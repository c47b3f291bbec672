"""Peak device memory in use after the window, in MB (10^6 bytes):
``memory_stats()["peak_bytes_in_use"]`` of the fullest chip used.  The
benchmark keeps nothing of its own on the device (its snapshot for the
check goes to the host), so the peak is the fog's state and the chunk
program's temporaries."""


def read(run):
    return run.peak_bytes / 1e6 if run.peak_bytes else None
