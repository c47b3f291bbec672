"""The chunk program's temporaries in MB (10^6 bytes), as XLA's memory
analysis of the compiled program gives them (``temp_size_in_bytes``).
``peak_bytes_in_use`` does not show them; they decide whether a size fits
the chip."""


def read(run):
    return run.temp_bytes / 1e6 if run.temp_bytes else None
