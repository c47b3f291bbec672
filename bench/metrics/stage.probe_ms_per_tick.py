"""Device time of the fused tick's ops in the probe stage (``stage.probe``: the
fog probe, response mask, winner election, payload gather and LRU touch, the
fill lines, the staleness count), by exclusive op time, per simulated tick
over the chunks of the traced window. None where the stage owns no op."""
from harness.stages import stage_ms_per_tick


def read(run):
    return stage_ms_per_tick(run, "probe")
