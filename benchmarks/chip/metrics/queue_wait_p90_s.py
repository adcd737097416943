"""Scheduler: 90th percentile over the window's requests of the wait from
a request's due time to its admission (``admitted_at``, set by the
engine's admit stage), a failed request counting as an endless wait.
The part of the time to first token spent queued, before any prefill."""
import math


def read(rec):
    v = rec["window_metrics"]["queue_wait_p90_s"]
    return v if math.isfinite(v) else None
