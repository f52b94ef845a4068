"""The replica the serve cells deploy: the class `serve.LLMDeployment` wraps,
with side doors for what only the process that holds the chip can do.

The request path (`generate`, the engine behind it) is inherited untouched.
The additions are never on it: the device as jax reports it and its memory
peak (the last line needs them from every run, and the program's `stats()`
does not carry them), the profiler's start and stop (only the process that
holds the chip can trace it), and the reference check on the very weights
that were served.  The class is reachable today only as
`serve.LLMDeployment._cls_or_fn`; a public name and a profiler hook are
asked of the `tracing` issue (PERF.md section 7).
"""

from __future__ import annotations

from ray_tpu import serve

_Base = serve.LLMDeployment._cls_or_fn


class BenchLLM(_Base):

    def device_report(self) -> dict:
        import jax
        devices = jax.local_devices()
        stats = [d.memory_stats() or {} for d in devices]
        from benchmark import manifest
        return dict(manifest.memory_report(stats),
                    platform=devices[0].platform,
                    kind=devices[0].device_kind, count=len(devices))

    def start_trace(self, trace_dir: str) -> bool:
        import jax
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        return True

    def stop_trace(self) -> bool:
        import jax
        jax.profiler.stop_trace()
        return True

    def reference_check(self, reference: str, samples: list) -> list:
        """For each (prompt, output) sample, the plain reference's verdict on
        every served token: (gaps, ranks) as `served_token_gaps` gives them,
        on the parameters this replica served with."""
        from benchmark import manifest
        ref = manifest.module("reference", reference)
        return [ref.served_token_gaps(self._engine.params, p, o)
                for p, o in samples]


def deployment(max_concurrent_queries: int):
    """`BenchLLM` under the program's own deployment name."""
    return serve.deployment(
        name="llm", max_concurrent_queries=max_concurrent_queries)(BenchLLM)
