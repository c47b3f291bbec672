"""Device time of the fused tick's ops in the plan stage (``stage.plan``:
request generation, the write rows, the channel's advance, churn), by
exclusive op time, per simulated tick over the chunks of the traced window.
None where the stage owns no op."""
from harness.stages import stage_ms_per_tick


def read(run):
    return stage_ms_per_tick(run, "plan")
