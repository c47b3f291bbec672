"""Device time of the fused tick's ops in the upsert stage (``stage.upsert``:
every ``flic.insert_rows``, of the write waves and of the read fill), by
exclusive op time, per simulated tick over the chunks of the traced window.
None where the stage owns no op."""
from harness.stages import stage_ms_per_tick


def read(run):
    return stage_ms_per_tick(run, "upsert")
