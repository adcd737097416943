"""Executor and pipeline: share of the traced window in which the chip was
idle under no ``serve.*`` or ``pipe.*`` span while a request was in
flight — the time between stages: scheduling, a worker's wake-up, the
GIL. The third part of ``device_idle_frac`` (``idlesplit.py``). Notes the
part of it spent in an accelerator worker's ``executor.sleep``, how many
such sleeps reached the notifier's 1 s backstop, and the rest of the idle
time, the traffic's lull with no request in flight, which no metric
reads. Silent where the window holds no ``serve.*`` span."""
import idlesplit


def read(rec):
    parts = idlesplit.split(rec)
    if parts is None:
        return None
    rec.setdefault("notes", []).append(
        f"idle_handoff_frac: {parts['handoff_sleep_s']:.6g} s of it under "
        f"an accel executor.sleep, {parts['backstop_sleeps']} sleeps of "
        f">= {idlesplit.BACKSTOP_S} s; lull (idle, no request in flight) "
        f"{parts['lull']:.6g} of the window")
    return parts["handoff"]
