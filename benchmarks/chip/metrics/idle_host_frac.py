"""Engine: share of the traced window in which the chip was idle under a
``serve.*`` or ``pipe.*`` span other than a read-back, on any thread —
host work of the serving engine (admission, batch building, launches,
growth, bookkeeping) that the chip waits for. The second part of
``device_idle_frac`` (``idlesplit.py``). Silent where the window holds no
``serve.*`` span."""
import idlesplit


def read(rec):
    parts = idlesplit.split(rec)
    return None if parts is None else parts["host"]
