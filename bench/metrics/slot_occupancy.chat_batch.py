"""Percent of decode slot-steps that served a live request, from the
engine's ``serve.busy_slot_steps_total`` and ``serve.total_slot_steps_total``
counters over the window (engine layer)."""


def compute(f):
    w = f.window
    if not w.total_slot_steps:
        return None
    return 100.0 * w.busy_slot_steps / w.total_slot_steps
