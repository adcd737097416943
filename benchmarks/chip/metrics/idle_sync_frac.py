"""Device runtime, read-back: share of the traced window in which the chip
was idle while a ``serve.sync`` or ``serve.window_sync`` span covered it —
the host blocked on a read-back of the chip's results while the chip had
nothing to do. The first part of ``device_idle_frac`` (``idlesplit.py``).
Silent where the window holds no ``serve.*`` span."""
import idlesplit


def read(rec):
    parts = idlesplit.split(rec)
    return None if parts is None else parts["sync"]
