"""Device time of the fused tick's ops in no stage (the scan's row aggregation
and loop control, the entry computation's carry copies, the scalar
accounting of section 6), by exclusive op time, per simulated tick over the
chunks of the traced window."""
from harness.stages import stage_ms_per_tick


def read(run):
    return stage_ms_per_tick(run, "unscoped")
