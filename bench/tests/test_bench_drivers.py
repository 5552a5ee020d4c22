"""Each kind's driver end to end on the CPU at a small size, through the
harness's own ``execute`` (the look for a chip is ``bench/run.py``'s)."""

import pytest

from bench.tests import small


@pytest.mark.parametrize("cell", ["qwen2-1.5b.long_prompt",
                                  "qwen2-1.5b.chat_batch"])
def test_driver_end_to_end(cell):
    line = small.run(small.small_ctx(cell))
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    m = line["metrics"]
    assert "setup_s" in m and m["setup_s"]["value"] > 0
    if cell.endswith("long_prompt"):
        assert m["ttft_p50_s"]["value"] > 0 and m["tpot_p50_s"]["value"] > 0
    else:
        assert m["output_tokens_per_s"]["value"] > 0
    checks = line["checks"]
    assert list(line)[-1] == "checks"
    assert checks["widest_gap"]["value"] <= checks["widest_gap"]["limit"]
    assert checks["unchecked"]["value"] == 0
