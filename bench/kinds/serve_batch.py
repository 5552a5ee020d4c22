"""Offline batch serving: every request is due at t = 0, more than the
window can finish, so the engine's slots never empty; the window's end
cuts what is still queued or decoding.

End-to-end: ``output_tokens_per_s``, every token emitted (those of the
cut requests too) over the time from the window's open to its last
result.
"""

from bench import serving


def end_to_end(w, mix):
    return {"output_tokens_per_s":
            sum(len(o.tokens) for o in w.outcomes) / w.t_end}


def attempted_failed(w):
    started = [o for o in w.outcomes if o.t_first is not None]
    return len(started), sum(o.status in ("failed", "shed") for o in started)


def run(ctx):
    return serving.run_cell(ctx, end_to_end, attempted_failed)
