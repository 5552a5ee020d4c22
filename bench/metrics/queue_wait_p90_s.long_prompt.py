"""90th percentile of queue wait: from a request's due time to the start
of its admission, from the engine's ``admit`` spans (engine layer).

Only admissions that ended before the profiler was switched on count:
starting the device trace stalls the host loop for seconds, and the
requests behind that stall wait for the tracer, not for the engine."""

from bench.serving import percentile


def compute(f):
    t_on = f.window.t_trace[0]
    waits = [rec["ts"] - rec["arrival"] for rec in f.window.spans
             if rec.get("kind") == "span" and rec["name"] == "admit"
             and rec["ts"] + rec["dur"] < t_on]
    return percentile(waits, 90) if waits else None
