"""Open-loop serving: requests arrive on a schedule whether or not earlier
ones finished; the window's end stops arrivals and the run drains.

End-to-end, over every request due in the window: the median and the 90th
percentile of the time from its due time to its first token (``ttft_*``;
a request that never gets one counts as the drain cap), and over requests
with two tokens or more, of the time per output token after the first
(``tpot_*``). A cell reports those that ``BENCHMARK.json`` names for it;
the sweep reads them all.
"""

from bench import serving


def end_to_end(w, mix):
    cut = w.seconds + mix["drain_cap_s"]
    ttft = [(o.t_first if o.t_first is not None else cut) - o.arrival
            for o in w.outcomes]
    tpot = [(o.t_done - o.t_first) / (len(o.tokens) - 1)
            for o in w.outcomes
            if o.t_first is not None and o.t_done is not None
            and len(o.tokens) > 1]
    return {"ttft_p50_s": serving.percentile(ttft, 50),
            "ttft_p90_s": serving.percentile(ttft, 90),
            "tpot_p50_s": serving.percentile(tpot, 50),
            "tpot_p90_s": serving.percentile(tpot, 90)}


def attempted_failed(w):
    return len(w.outcomes), sum(o.status != "ok" for o in w.outcomes)


def run(ctx):
    return serving.run_cell(ctx, end_to_end, attempted_failed)
