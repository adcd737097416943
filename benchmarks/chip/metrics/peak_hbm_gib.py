"""Cache manager and device memory: the chip's peak bytes in use
(``memory_stats()["peak_bytes_in_use"]``) after the window, in GiB:
weights, the KV pool and the programs' temporaries."""


def read(rec):
    b = rec["memory_peak_bytes"]
    return b / 2 ** 30 if b else None
