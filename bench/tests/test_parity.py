"""The model lookup (``bench/model.py``) gives the numbers the benchmark
read before configurations named their model: every count, the weights'
shapes and dtypes at full width, and, at the tiny widths of
``test_reference.tiny_cfg``, the weights, the served adapters and the
reference logits to the bit. The constants were computed with the
benchmark's dense code as it stood before the lookup."""

import hashlib
import json
import os

import jax
import numpy as np
import pytest

from bench import counts as C
from bench import model as M
from bench import weights as W
from bench.tests.test_reference import tiny_cfg
from bench.tests.tiny import ROOT

PARENT = {
    "stablelm-1.6b": {
        "matmul_params": 1233125376,
        "attention_flops_1": 196608.0,
        "decode_token_flops": [2879062016.0, 2954362880.0, 3180855296.0],
        "prefill_flops": [2879062016.0, 317715906560.0, 2629033263104.0],
        "adapt_token_flops": 826802176.0,
        "skip_serve_call": (18874368.0, 51560448.0),
        "skip_adapt_bwd_call": (75497472.0, 28442624.0),
        "shapes": [
            ("['embed']['table']", (100352, 2048), 'bfloat16'),
            ("['final_norm']['bias']", (2048,), 'float32'),
            ("['final_norm']['scale']", (2048,), 'float32'),
            ("['head']['table']", (100352, 2048), 'bfloat16'),
            ("['stack']['periods'][0]['attn']['wk']", (24, 2048, 32, 64), 'bfloat16'),
            ("['stack']['periods'][0]['attn']['wo']", (24, 32, 64, 2048), 'bfloat16'),
            ("['stack']['periods'][0]['attn']['wq']", (24, 2048, 32, 64), 'bfloat16'),
            ("['stack']['periods'][0]['attn']['wv']", (24, 2048, 32, 64), 'bfloat16'),
            ("['stack']['periods'][0]['ffn']['w_down']", (24, 5632, 2048), 'bfloat16'),
            ("['stack']['periods'][0]['ffn']['w_gate']", (24, 2048, 5632), 'bfloat16'),
            ("['stack']['periods'][0]['ffn']['w_up']", (24, 2048, 5632), 'bfloat16'),
            ("['stack']['periods'][0]['norm1']['bias']", (24, 2048), 'float32'),
            ("['stack']['periods'][0]['norm1']['scale']", (24, 2048), 'float32'),
            ("['stack']['periods'][0]['norm2']['bias']", (24, 2048), 'float32'),
            ("['stack']['periods'][0]['norm2']['scale']", (24, 2048), 'float32'),
        ],
        "tiny_params": {
            "['embed']['table']": 262.68904664983603,
            "['final_norm']['bias']": 5.552432641852647,
            "['final_norm']['scale']": 64.85758811235428,
            "['head']['table']": 261.24655496966267,
            "['stack']['periods'][0]['attn']['wk']": 814.1473923019403,
            "['stack']['periods'][0]['attn']['wo']": 829.4585824273745,
            "['stack']['periods'][0]['attn']['wq']": 826.706302075213,
            "['stack']['periods'][0]['attn']['wv']": 813.4117119767689,
            "['stack']['periods'][0]['ffn']['w_down']": 1163.7253254823327,
            "['stack']['periods'][0]['ffn']['w_gate']": 1641.8093851572326,
            "['stack']['periods'][0]['ffn']['w_up']": 1647.518713883724,
            "['stack']['periods'][0]['norm1']['bias']": 9.570227227988653,
            "['stack']['periods'][0]['norm1']['scale']": 128.78177124261856,
            "['stack']['periods'][0]['norm2']['bias']": 10.54237367468886,
            "['stack']['periods'][0]['norm2']['scale']": 127.94366180896759,
        },
        "tiny_adapters": {
            "['A']": 200.6995194941137,
            "['B']": 411.5687529482675,
        },
        "tiny_logits": 401.94833997097885,
        "tiny_logits_sha256": '369e073266f85851b02ff4ac96ee2d63a5d2c677b7ca47f4846bcd282e78536d',
    },
    "musicgen-medium": {
        "matmul_params": 1358954496,
        "attention_flops_1": 294912.0,
        "decode_token_flops": [2726854656.0, 2839805952.0, 3179544576.0],
        "prefill_flops": [2726854656.0, 350335795200.0, 2937917276160.0],
        "adapt_token_flops": 19660800.0,
        "skip_serve_call": (28311552.0, 77303808.0),
        "skip_adapt_bwd_call": (113246208.0, 42565632.0),
        "shapes": [
            ("['embed']['table']", (2048, 1536), 'bfloat16'),
            ("['final_norm']['bias']", (1536,), 'float32'),
            ("['final_norm']['scale']", (1536,), 'float32'),
            ("['head']['table']", (2048, 1536), 'bfloat16'),
            ("['stack']['periods'][0]['attn']['wk']", (48, 1536, 24, 64), 'bfloat16'),
            ("['stack']['periods'][0]['attn']['wo']", (48, 24, 64, 1536), 'bfloat16'),
            ("['stack']['periods'][0]['attn']['wq']", (48, 1536, 24, 64), 'bfloat16'),
            ("['stack']['periods'][0]['attn']['wv']", (48, 1536, 24, 64), 'bfloat16'),
            ("['stack']['periods'][0]['ffn']['w_down']", (48, 6144, 1536), 'bfloat16'),
            ("['stack']['periods'][0]['ffn']['w_up']", (48, 1536, 6144), 'bfloat16'),
            ("['stack']['periods'][0]['norm1']['bias']", (48, 1536), 'float32'),
            ("['stack']['periods'][0]['norm1']['scale']", (48, 1536), 'float32'),
            ("['stack']['periods'][0]['norm2']['bias']", (48, 1536), 'float32'),
            ("['stack']['periods'][0]['norm2']['scale']", (48, 1536), 'float32'),
        ],
        "tiny_params": {
            "['embed']['table']": 262.6894974262688,
            "['final_norm']['bias']": 4.753473921795376,
            "['final_norm']['scale']": 63.09524917602539,
            "['head']['table']": 260.9550151183955,
            "['stack']['periods'][0]['attn']['wk']": 814.1473923019403,
            "['stack']['periods'][0]['attn']['wo']": 829.4585824273745,
            "['stack']['periods'][0]['attn']['wq']": 826.706302075213,
            "['stack']['periods'][0]['attn']['wv']": 813.4117119767689,
            "['stack']['periods'][0]['ffn']['w_down']": 1163.7253254823327,
            "['stack']['periods'][0]['ffn']['w_up']": 1647.518713883724,
            "['stack']['periods'][0]['norm1']['bias']": 9.570227227988653,
            "['stack']['periods'][0]['norm1']['scale']": 128.78177124261856,
            "['stack']['periods'][0]['norm2']['bias']": 10.54237367468886,
            "['stack']['periods'][0]['norm2']['scale']": 127.94366180896759,
        },
        "tiny_adapters": {
            "['A']": 200.6995194941137,
            "['B']": 411.5687529482675,
        },
        "tiny_logits": 395.15199170275446,
        "tiny_logits_sha256": 'f9beebe29dbbc6796c928420fb6b14a6054a9511e075a1320f2e1fb5668233d4',
    },
}

CONFIGS = sorted(PARENT)


def full_cfg(base: str) -> dict:
    with open(os.path.join(ROOT, "bench", "configs", f"{base}.json")) as f:
        return json.load(f)


def abs_sums(tree) -> dict:
    """float64 sum of |leaf| for every leaf, by its path."""
    return {jax.tree_util.keystr(k): float(np.sum(np.abs(np.asarray(v, np.float64))))
            for k, v in jax.tree_util.tree_leaves_with_path(tree)}


@pytest.mark.parametrize("base", CONFIGS)
def test_counts_equal_parent(base):
    cfg, want = full_cfg(base), PARENT[base]
    assert C.matmul_params(cfg) == want["matmul_params"]
    assert C.attention_flops(cfg, 1) == want["attention_flops_1"]
    assert ([C.decode_token_flops(cfg, p) for p in (0, 383, 1535)]
            == want["decode_token_flops"])
    assert [C.prefill_flops(cfg, n) for n in (1, 128, 1024)] == want["prefill_flops"]
    assert C.adapt_token_flops(cfg) == want["adapt_token_flops"]
    assert C.skip_serve_call(cfg, 12, 16) == want["skip_serve_call"]
    assert C.skip_adapt_bwd_call(cfg, 32, 4) == want["skip_adapt_bwd_call"]


@pytest.mark.parametrize("base", CONFIGS)
def test_full_width_shapes_equal_parent(base):
    cfg = full_cfg(base)
    shapes = jax.eval_shape(lambda: M.model(cfg).make_params(cfg, 0))
    got = [(jax.tree_util.keystr(k), tuple(v.shape), str(v.dtype))
           for k, v in jax.tree_util.tree_leaves_with_path(shapes)]
    assert got == PARENT[base]["shapes"]


@pytest.mark.parametrize("base", CONFIGS)
def test_tiny_weights_adapters_logits_equal_parent(base):
    cfg, want = tiny_cfg(base), PARENT[base]
    params = M.model(cfg).make_params(cfg, 3)
    ad = W.make_adapters(cfg, 3, 2)
    assert abs_sums(params) == want["tiny_params"]
    assert abs_sums(ad) == want["tiny_adapters"]
    tokens = jax.random.randint(jax.random.key(0), (12,), 0, cfg["vocab_size"])
    lg = np.asarray(M.reference(cfg).sequence_logits(
        params, cfg, tokens, ad["A"][1], ad["B"][1]))
    assert float(np.sum(np.abs(lg.astype(np.float64)))) == want["tiny_logits"]
    assert hashlib.sha256(lg.tobytes()).hexdigest() == want["tiny_logits_sha256"]
