"""What the benchmark reads from JAX and from the program under test -- and
nothing else: JAX's compile events, the program's metrics registry and
flight-recorder spans, device memory, and the compiled step's HLO text (for
the trace -> named-scope join). Every accessor of program internals is here
so a refactor of the program breaks one file.
"""
from __future__ import annotations


class CompileWatch:
    """Sums JAX's own compile events: seconds inside the backend compile (a
    persistent-cache read counts as its retrieval time), the number of
    backend compiles, and the persistent cache's hit / miss events. Copied
    from ``chip_smoke.py`` (proven on the chip in PR 21)."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"backend_s": self.seconds, "backend_compiles": self.compiles,
                "cache_hits": self.hits, "cache_misses": self.misses}


def counter(name: str, **labels) -> int:
    """A counter of the program's own metrics registry."""
    from paddle_tpu.observability.metrics import REGISTRY
    return int(REGISTRY.counter(name, **labels).value)


def executor_compiles() -> int:
    return counter("executor_cache_misses_total", cache="compile")


def executor_runs() -> int:
    return counter("executor_runs_total")


def spans(t0: float, t1: float) -> list:
    """The program's flight-recorder spans that started in [t0, t1)
    (``time.perf_counter`` seconds): ``(name, start, duration)``."""
    from paddle_tpu.observability import timeline
    return [(s[0], s[2], s[3]) for s in timeline.spans() if t0 <= s[2] < t1]


def seed_programs(startup, main, seed: int) -> None:
    """Make the run's weights and dropout masks depend on ``--seed`` without
    changing a compiled program: a Program's PRNG key is
    fold_in(PRNGKey(random_seed), run counter), where ``random_seed`` is a
    constant of the HLO (another value is another program, and a miss of the
    compilation cache in every run) and the run counter is an argument. So
    ``random_seed`` stays fixed and the counters start from the seed."""
    startup._rng_run_counter = seed % (1 << 31)
    main._rng_run_counter = seed * 1000003 % (1 << 31)


def step_hlo(exe) -> str:
    """Optimized HLO text of the executor's most recently compiled step."""
    return next(reversed(exe._cache.values())).executable.as_text()


def step_memory(exe) -> dict:
    """XLA's memory analysis of the most recently compiled step, bytes."""
    m = next(reversed(exe._cache.values())).executable.memory_analysis()
    return {"argument": int(m.argument_size_in_bytes),
            "output": int(m.output_size_in_bytes),
            "temp": int(m.temp_size_in_bytes),
            "alias": int(m.alias_size_in_bytes)}


def devices(chips: int) -> list:
    import jax
    return jax.devices()[:chips]


def bytes_in_use(devs) -> list:
    return [int((d.memory_stats() or {}).get("bytes_in_use", 0))
            for d in devs]


def peak_bytes(devs) -> int:
    """Peak bytes held on the fullest device. The TPU runtime keeps two
    pools: ``peak_bytes_in_use`` counts the buffers the process holds (state,
    feeds, fetches) and ``peak_bytes_reserved`` what running programs
    reserve for their temporaries -- on the v5e the first alone stayed at the
    size of the state while XLA's own analysis put 5.7 GB of temporaries
    beside it (PERF.md section 6, PR 22). The peak is their sum. 0 where the
    backend keeps no statistics, as the CPU does."""
    def peak(d):
        m = d.memory_stats() or {}
        return int(m.get("peak_bytes_in_use", 0)) \
            + int(m.get("peak_bytes_reserved", 0))
    return max(peak(d) for d in devs)


def tuning_state() -> dict:
    """The autotuner's mode and persisted decisions: a persisted
    ``fused_attention.backend`` decision changes what a cell runs."""
    from paddle_tpu.tuning import cache as tune_cache
    tune_cache.CACHE.load()
    return {"mode": tune_cache.mode(), "path": tune_cache.CACHE.path,
            "decisions": len(tune_cache.CACHE.items())}
