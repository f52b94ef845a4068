"""What the router, the replica actor and the stream tickets add to the time to
first token: over a sample of requests sent under a trace context, the
median of (first token at the client - the instant the request was actually
sent) - (the engine's own `engine/prefill` span of that request, submit to
first emitted token, queue wait in the engine included), paired by trace
id."""

from __future__ import annotations


def read(run: dict):
    from benchmark import metrics, readers
    engine = readers.span_seconds(run.get("engine_events", []), "prefill")
    diffs = []
    for r in run.get("counted", []):
        dur = engine.get(r.get("trace_id"))
        if dur is not None and r["token_times"] and not r.get("error"):
            diffs.append((r["token_times"][0] - r["sent"] - dur) * 1e3)
    return metrics.percentile(diffs, 50) if len(diffs) >= 5 else None
