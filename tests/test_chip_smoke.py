"""chip_smoke.py's phases at reduced size on the CPU (interpret-mode kernels).

The chip run drives the same functions at full qwen2-1.5b width; here they
run on ``reduced_config("qwen2-1.5b")`` so that a broken path, argument or
check fails before any chip time is spent.
"""

import importlib.util
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.models import build_model
from repro.serve.engine import Result

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LENS = (40, 32, 13)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def run(smoke, tmp_path_factory):
    cfg = reduced_config("qwen2-1.5b")
    # one of two blocks through ADMM: the cut path the chip run takes
    result = smoke.prune_phase(cfg, seed=0, admm_layers=1, tile_block=32)
    out = str(tmp_path_factory.mktemp("smoke") / "artifact")
    art = smoke.pack_phase(result, cfg, out, tune_for=(4,) + LENS)
    model = build_model(cfg)
    reqs = smoke.make_requests(cfg, LENS, 4, 0)
    dense, _ = smoke.serve_phase(model, art, reqs, packed=False, batch=4,
                                 chunk_steps=4)
    packed, engine = smoke.serve_phase(model, art, reqs, packed=True,
                                       batch=4, chunk_steps=4)
    return dict(cfg=cfg, result=result, art=art, out=out, model=model,
                reqs=reqs, dense=dense, packed=packed, engine=engine)


class TestPhases:
    def test_prune_cut_keeps_four_of_eight_in_every_block(self, run):
        res = run["result"]
        assert len(res.history["loss"]) == 2            # ADMM ran on block 0
        w = np.asarray(res.params["blocks"]["mlp"]["w_up"], np.float32)
        for layer in w:                                 # block 1: projection
            groups = (layer != 0).reshape(-1, 8, layer.shape[-1])
            assert (groups.sum(axis=1) <= 4).all()
            assert (layer != 0).mean() == pytest.approx(0.5, abs=0.01)
        assert res.provenance["data"] == "synthetic"

    def test_pack_saves_and_packs_every_block_gemm(self, run):
        s = run["art"].summary()
        assert s["packed_leaves"] >= 7 and s["bytes_ratio"] > 1.5
        assert os.path.isfile(os.path.join(run["out"], "artifact.json"))
        assert run["art"].meta["tuned_plans"]

    def test_serve_checks_pass(self, smoke, run):
        agree = smoke.check_serve(run["dense"], run["packed"], run["engine"])
        assert agree["requests"] == len(LENS)
        assert agree["tokens"] == 4 * len(LENS)

    def test_prefill_logits_agree(self, smoke, run):
        r = smoke.check_prefill(run["model"], run["art"], run["reqs"][0].prompt,
                                expect_kernels=False)
        assert r["S"] == LENS[0]
        for mode in ("packed", "pallas"):
            assert r[mode]["rel_l2"] <= smoke.LOGIT_RTOL

    def test_pin_prefill_plan_resolves_pallas(self, smoke, run):
        from repro.sparse import tune

        pinned = smoke.pin_prefill_plan(run["art"], (LENS[0],))
        pt = pinned.packed["blocks"]["mlp"]["w_up"]
        plan = tune.plan_from_meta(pt, "matmul", LENS[0])
        assert plan.to_str() == smoke.PALLAS_PLAN


class TestChecks:
    def test_failed_status_is_refused(self, smoke, run):
        bad = [Result(uid=r.uid, tokens=r.tokens, status="failed")
               for r in run["packed"]]
        with pytest.raises(smoke.SmokeFailure, match="packed requests"):
            smoke.check_serve(run["dense"], bad, run["engine"])

    def test_token_agreement_finds_first_divergence(self, smoke):
        d = [Result(0, [1, 2, 3]), Result(1, [4, 5, 6])]
        p = [Result(0, [1, 2, 3]), Result(1, [4, 9, 6])]
        a = smoke.token_agreement(d, p)
        assert a["identical_requests"] == 1
        assert a["first_divergence"] == {1: 1}
        assert a["prefix_tokens_agreeing"] == 4

    def test_kernel_calls_reads_custom_call_names(self, smoke):
        hlo = ('  %pattern_gemm.15 = bf16[256,256]{1,0} custom-call(%a), '
               'custom_call_target="tpu_custom_call", backend_config={}\n'
               '  %flash_attention = bf16[12,512,128]{2,1,0} custom-call(%b)'
               ', custom_call_target="tpu_custom_call"\n'
               '  %dot.3 = f32[8,8]{1,0} dot(%c, %d)\n')
        assert smoke.kernel_calls(hlo) == {"pattern_gemm": 1,
                                           "flash_attention": 1}

    def test_admm_layer_budget_cuts_full_width_on_16gb(self, smoke):
        cfg = get_config("qwen2-1.5b")
        n = smoke.admm_layer_budget(cfg, 16 * 2 ** 30)
        assert 1 <= n < cfg.num_layers
        assert smoke.admm_layer_budget(cfg, 2 ** 40) == cfg.num_layers


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       capture_output=True, text=True, env=env, timeout=120)
    assert p.returncode == 2
    assert '"ok"' not in p.stdout
    assert "no TPU" in p.stderr
