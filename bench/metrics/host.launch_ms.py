"""Mean host time per chunk of the runtime's execute call
(``PJRT_LoadedExecutable_Execute``) inside the traced window: argument
handling, output allocation and the launch, within the ``dispatch`` span."""


def read(run):
    ss = getattr(run, "stage_summary", None)
    if ss is None or not ss.launches or not ss.chunks:
        return None
    return ss.launch_s * 1e3 / ss.chunks
