"""Set up one cell, drive its timed window of chunks, and keep the record.

A chunk is one call of the cell's engine's chunk program
(``harness/engines/<engine>.py``: ``chunk_fn``), with the state donated and
carried from the previous chunk; it ends when its aggregated ``TickMetrics``
row is on the host.  Warm-up runs the same calls from the engine's initial
state before the window, so the window continues one simulation that starts
at tick 0.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import os
import time

import numpy as np

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclasses.dataclass
class RunRecord:
    """Everything a metric reader or the correctness check reads."""

    cell: object
    seed: int
    seconds: float
    t_start: float                       # process start (perf_counter)
    setup_s: float = 0.0
    setup_marks: dict = dataclasses.field(default_factory=dict)  # stage -> s
    setup_compile_s: float = 0.0         # backend compile or cache load, set-up
    setup_compiles: int = 0
    window_compiles: int = 0
    spans: list = dataclasses.field(default_factory=list)  # (name, t0, t1)
    chunk_s: list = dataclasses.field(default_factory=list)
    window_s: float = 0.0
    ticks: int = 0                       # ticks run inside the window
    rows: list = dataclasses.field(default_factory=list)   # every chunk's row
    snap_chunk: int = 0                  # chunks from tick 0 to the snapshot
    peak_bytes: int | None = None
    temp_bytes: int | None = None        # the chunk program's temporaries
    trace_summary: object = None

    def mark(self, stage: str) -> None:
        """Seconds from process start to the end of a set-up stage."""
        self.setup_marks[stage] = time.perf_counter() - self.t_start


class CompileCounter:
    """Sums JAX's backend-compile events (a persistent-cache hit is one too:
    the event spans the cache read)."""

    def __init__(self):
        self.secs = 0.0
        self.count = 0

    def __call__(self, event: str, secs: float, **_) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.secs += secs
            self.count += 1


def use_compile_cache(root) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR`` where
    it is set, else ``<checkout>/.jax_cache`` (a fixed path, so every run in
    one checkout hits).  Every program is written to it, however short its
    compile, so a later run loads instead of compiling."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def snapshot_chunk(cell, seed: int) -> int:
    """The chunk (counted from tick 0) whose state and rows the plain
    reference replays to; drawn from the seed within the cell's range."""
    lo, hi = cell.ref_chunks
    return int(np.random.default_rng(seed % 2**63).integers(lo, hi))


def build(cell, cfg, seed: int, devices):
    """The cell's initial state for the seed, which its engine builds on
    the first ``cell.chips`` of ``devices``."""
    return cell.engine.build(cfg, seed, devices[:cell.chips])


def program_temp_bytes(cell, cfg, state) -> int | None:
    """The chunk program's temporaries on a device, as XLA's memory analysis
    of the compiled program gives them (an SPMD program's are per device).
    The allocator's peak does not show them; they decide whether a size fits
    the chip.  Lowering again loads the program from the compile cache, so
    only a traced run reads this, after its window."""
    compiled = cell.engine.lower(cfg, cell.chunk_ticks, state)
    analysis = compiled.memory_analysis()
    return None if analysis is None else int(analysis.temp_size_in_bytes)


def drive(record: RunRecord, cfg, counter: CompileCounter, devices, tracer=None):
    """Warm up, then run the window: chunks back to back for ``seconds``,
    and on until the snapshot chunk if that comes later.  Returns (final
    state, the state after the snapshot chunk as host arrays).  The snapshot
    is fetched to the host inside the window, so it takes no device memory;
    its chunk's time includes the fetch."""
    import jax

    cell = record.cell
    engine = cell.engine
    chunk = engine.chunk_fn(cfg, cell.chunk_ticks)
    state = build(cell, cfg, record.seed, devices)
    jax.block_until_ready(state)
    record.mark("state_built")
    record.snap_chunk = snapshot_chunk(cell, record.seed)
    for _ in range(cell.warm_ticks // cell.chunk_ticks):
        state, row = chunk(state)
        record.rows.append(jax.device_get(row))
    record.mark("warmed_up")
    # Set-up leaves a large graph of long-lived objects (traced programs,
    # caches); a full garbage collection inside the window would walk all
    # of it.  Freeze it, so the window's collections see only its own rows.
    gc.collect()
    gc.freeze()
    record.setup_compile_s, record.setup_compiles = counter.secs, counter.count

    if tracer is not None:
        tracer.start()
    snapshot = None
    n_before = counter.count
    t0 = time.perf_counter()
    record.setup_s = t0 - record.t_start
    end = t0 + record.seconds
    t_done = t0
    while t_done < end or len(record.rows) < record.snap_chunk:
        c0 = time.perf_counter()
        with _annotate(tracer, "dispatch"):
            state, row = chunk(state)
        c1 = time.perf_counter()
        with _annotate(tracer, "fetch"):
            record.rows.append(jax.device_get(row))
            if len(record.rows) == record.snap_chunk:
                snapshot = engine.state_leaves(state)
        t_done = time.perf_counter()
        record.spans.append(("dispatch", c0, c1))
        record.spans.append(("fetch", c1, t_done))
        record.chunk_s.append(t_done - c0)
    if tracer is not None:
        tracer.stop()
    record.window_s = t_done - t0
    record.ticks = len(record.chunk_s) * cell.chunk_ticks
    record.window_compiles = counter.count - n_before
    return state, snapshot


def _annotate(tracer, name: str):
    if tracer is None:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)
