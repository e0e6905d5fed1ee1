"""Tests for the launch layer: HLO analysis, analytic FLOPs, shapes,
roofline record analysis, and (in a subprocess) sharding-spec derivation on
a real multi-device mesh."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.configs import get_config, list_archs
from repro.launch import hlo_analysis as H
from repro.launch.flops import model_flops
from repro.launch.shapes import SHAPES, all_cells, cell_supported, live_cells


class TestShapes:
    def test_cell_counts(self):
        assert len(all_cells()) == 40  # 10 archs x 4 shapes
        assert len(live_cells()) == 32  # 8 documented long_500k skips

    def test_long500k_only_subquadratic(self):
        ok, _ = cell_supported("xlstm-350m", "long_500k")
        assert ok
        ok, why = cell_supported("gemma3-27b", "long_500k")
        assert not ok and "sub-quadratic" in why

    def test_shape_table(self):
        assert SHAPES["train_4k"].kind == "train"
        assert SHAPES["decode_32k"].kind == "decode"
        assert SHAPES["long_500k"].batch == 1


SYNTH_HLO = textwrap.dedent("""\
HloModule test, entry_computation_layout={()->f32[]}

%body (p: (s32[], f32[128,256])) -> (s32[], f32[128,256]) {
  %p = (s32[], f32[128,256]) parameter(0)
  %ar = f32[128,256]{1,0} all-reduce(%gte1), replica_groups={}, to_apply=%add
  %dot1 = f32[128,512]{1,0} dot(f32[128,256]{1,0} %ar, f32[256,512]{1,0} %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %t = (s32[], f32[128,256]) tuple(%iv, %ar)
}

%cond (p2: (s32[], f32[128,256])) -> pred[] {
  %p2 = (s32[], f32[128,256]) parameter(0)
  %c = s32[] constant(10)
  ROOT %lt = pred[] compare(%iv2, %c), direction=LT
}

ENTRY %main () -> f32[] {
  %ag = f32[64,64]{1,0} all-gather(%x), dimensions={0}
  %w0 = while((s32[], f32[128,256]) %init), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"10"}}
  %dot0 = f32[32,32]{1,0} dot(f32[32,16]{1,0} %a, f32[16,32]{1,0} %b), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %r = f32[] constant(0)
}
""")


class TestHLOAnalysis:
    def test_collectives_with_loop_multiplier(self):
        stats = H.analyze_collectives(SYNTH_HLO)
        # all-gather outside loop: 64*64*4 = 16384 B.
        # all-reduce inside 10-trip loop: 128*256*4 * 2 (AR) * 10 = 2621440 B.
        assert stats.per_op_bytes["all-gather"] == pytest.approx(16384)
        assert stats.per_op_bytes["all-reduce"] == pytest.approx(128 * 256 * 4 * 2 * 10)
        assert stats.count == 2

    def test_dot_flops_with_loop_multiplier(self):
        flops = H.analyze_dot_flops(SYNTH_HLO)
        # dot0: 2*32*32*16 = 32768; dot1 in loop: 2*128*512*256*10.
        assert flops == pytest.approx(32768 + 2 * 128 * 512 * 256 * 10)

    def test_shape_bytes_parsing(self):
        assert H._first_shape_bytes("  %x = bf16[2,3]{1,0} add(...)") == 12
        assert H._first_shape_bytes("  %x = (f32[4], s8[8]) tuple(...)") == 24


class TestModelFlops:
    @pytest.mark.parametrize("arch", list_archs())
    def test_positive_and_ordered(self, arch):
        cfg = get_config(arch)
        train = model_flops(cfg, "train_4k", "train")
        prefill = model_flops(cfg, "prefill_32k", "prefill")
        decode = model_flops(cfg, "decode_32k", "decode")
        assert train > 0 and prefill > 0 and decode > 0
        # One decode token is vastly cheaper than a full train step.
        assert decode < train / 100

    def test_cached_step_is_much_cheaper(self):
        cfg = get_config("gemma3-27b")
        full = model_flops(cfg, "train_4k", "train")
        cached = model_flops(cfg, "train_4k", "finetune_cached")
        assert cached < full / 10

    def test_train_matches_6nd_rule(self):
        # Dense arch: train flops ~ 6*N*D within 2x (attention + readout).
        cfg = get_config("gemma-7b")
        tokens = 256 * 4096
        six_nd = 6 * cfg.param_count() * tokens
        mf = model_flops(cfg, "train_4k", "train")
        assert 0.5 * six_nd < mf < 2.5 * six_nd


class TestRooflineRecords:
    def test_analyze_record_fields(self):
        from repro.launch.roofline import analyze_record

        rec = {
            "arch": "gemma-7b", "shape": "train_4k", "step": "train",
            "mesh": "16x16", "chips": 256, "dot_flops": 1e14,
            "bytes_accessed": 1e12, "collective_bytes": 1e11,
        }
        out = analyze_record(rec)
        assert out["dominant"] in ("compute", "memory", "collective")
        assert out["compute_s"] == pytest.approx(1e14 / 197e12)
        assert 0 < out["mfu_model"] <= 1.5
        assert out["step_time_s"] == max(
            out["compute_s"], out["memory_s"], out["collective_s"]
        )

    def test_shipped_dryrun_records_clean(self):
        path = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                            "dryrun_baseline.json")
        if not os.path.exists(path):
            pytest.skip("baseline sweep not present")
        with open(path) as f:
            recs = json.load(f)
        assert len(recs) == 64
        assert not any("error" in r for r in recs)
        meshes = {r["mesh"] for r in recs}
        assert meshes == {"16x16", "2x16x16"}


SPEC_PROG = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.configs import get_config, reduce_config
    from repro.models.lm import init_lm
    from repro.runtime import sharding as SH

    mesh = jax.make_mesh((2, 4), ("data", "model"))
    cfg = get_config("qwen2-moe-a2.7b")
    params_shape = jax.eval_shape(lambda k: init_lm(k, cfg), jax.random.key(0))
    specs = SH.param_specs(params_shape, mesh)
    flat = jax.tree_util.tree_flatten_with_path(specs)[0]
    by_path = { "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path): s
                for path, s in flat }
    # qwen: 60 experts % 4 == 0 on this mesh -> expert-sharded (leading
    # periods axis unsharded). On the 16-way production axis the same rule
    # falls back to sharding the expert FFN hidden dim.
    moe_gate = [s for k, s in by_path.items() if "moe/w_gate" in k][0]
    assert moe_gate == P(None, "model", None, None), moe_gate
    # attention heads 16 % 4 == 0 -> head-sharded.
    wq = [s for k, s in by_path.items() if "attn/wq" in k][0]
    assert wq == P(None, None, "model", None), wq
    # embed vocab-sharded.
    emb = by_path["embed/table"]
    assert emb == P("model", None), emb
    # zero1 upgrade: first replicated big axis gets 'data', idempotent.
    z1 = SH.zero1_specs(params_shape, specs, mesh)
    z2 = SH.zero1_specs(params_shape, z1, mesh)
    assert jax.tree.all(jax.tree.map(lambda a, b: a == b, z1, z2,
        is_leaf=lambda x: isinstance(x, P)))
    # fsdp specs: every big leaf sharded.
    f = SH.fsdp_param_specs(params_shape, mesh)
    big = [s for (path, s), l in zip(jax.tree_util.tree_flatten_with_path(f)[0],
           jax.tree.leaves(params_shape)) if l.size >= (1 << 16)]
    assert all(any(p is not None for p in s) for s in big)
    print("SPECS_OK")
    """
)


@pytest.mark.slow  # forces a fresh multi-device subprocess: ~8 min alone
class TestShardingSpecsMultiDevice:
    def test_param_specs_subprocess(self):
        env = dict(os.environ)
        env["PYTHONPATH"] = "src"
        env["JAX_PLATFORMS"] = "cpu"  # forced host devices, never a chip
        res = subprocess.run(
            [sys.executable, "-c", SPEC_PROG], capture_output=True, text=True,
            env=env, timeout=600,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        assert "SPECS_OK" in res.stdout, res.stdout + res.stderr
