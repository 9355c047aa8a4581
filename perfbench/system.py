"""The benchmark's one seam into the program under test.

Programs are built and compiled the way the program's ``Engine`` does it:
the registered workload at a preset plus overrides, the implementation
resolved by ``Engine._resolve_impl`` and forced while tracing by
``Engine._impl_context`` (inside ``Engine._compile_through_caches``), and
the executable kept in the engine's compile cache under the key
``Engine._bucket_key`` gives a (shape bucket, batch width). These are
private seams of the engine: the program has no public entry point that
hands out a compiled executable for inputs it did not make itself.

The inputs are the benchmark's own, made from the seed by the
configuration's reference module, so no program code decides what data the
yardstick runs on.
"""

from __future__ import annotations

import types

import jax


class Programs:
    """Compiles the program's executables; ``stage_us`` sums the engine's
    own ``compile`` stage timings over them."""

    def __init__(self) -> None:
        from repro.core.engine import Engine

        self.engine = Engine()
        self.stage_us: dict[str, float] = {}

    def compile(self, registry: str, preset: int, overrides: dict, impl: str,
                width: int, args: tuple):
        """The executable of ``registry`` at ``preset`` + ``overrides`` for
        ``args``; ``width`` > 1 vmaps the workload over a leading batch axis,
        as the engine's mixed-shape serve path does."""
        from repro.core.plan import Placement
        from repro.core.registry import get_benchmark

        engine = self.engine
        spec = get_benchmark(registry)
        workload = spec.build_preset(preset, **overrides)
        resolved, fallback = engine._resolve_impl(
            workload, types.SimpleNamespace(impl=impl), False
        )
        if resolved != impl:
            raise ValueError(
                f"{workload.name} cannot run impl={impl!r}: falls back to "
                f"{resolved!r} ({fallback})"
            )
        fn = workload.fn if width == 1 else jax.vmap(workload.fn)
        placement = Placement(devices=1, mode="replicate")
        key = engine._bucket_key(spec, preset, overrides, placement, impl, None, width)
        timings: dict[str, float] = {}
        with engine._timed_stage("compile", timings, bench=workload.name):
            entry = engine.cache.lookup(
                key,
                lambda: engine._compile_through_caches(
                    key, workload, fn, args,
                    pass_name=f"{workload.name}[{width}]", impl=impl,
                    tuned_params=None, use_disk=False,
                ),
            )
        for stage, us in timings.items():
            self.stage_us[stage] = self.stage_us.get(stage, 0.0) + us
        return entry.executable
