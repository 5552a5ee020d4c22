"""Median over the traced decode chunks of the time from the chunk's
device program ending on the chip to the end of its host
``decode_chunk.sync`` span: how long the host takes to see a finished
chunk (engine layer)."""

import statistics

from bench import engine_spans as es
from bench import readers
from bench import trace as tr


def compute(f):
    syncs = tr.Index(es.events(f, es.CHUNK_SYNC))
    lags = []
    for c in es.events(f, es.CHUNK):
        program = readers.program_in(f, c.start, c.end)
        sync = syncs.inside(c.start, c.end)
        if program is not None and sync:
            lags.append(sync[-1].end - program.end)
    return 1e3 * statistics.median(lags) if lags else None
