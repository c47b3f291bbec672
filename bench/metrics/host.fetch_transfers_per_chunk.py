"""Device-to-host transfers (``tpu::System::TransferFromDevice``) per chunk
inside the traced window: one per field of the chunk's metrics row."""


def read(run):
    ss = getattr(run, "stage_summary", None)
    if ss is None or not ss.chunks:
        return None
    return ss.transfers / ss.chunks
