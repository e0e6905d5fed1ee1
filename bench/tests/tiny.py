"""A tiny copy of the benchmark for CPU tests: the repository's ``bench``
files plus a tiny configuration and tiny versions of each cell's mix,
added only as new files and new ``BENCHMARK.json`` entries."""

import copy
import json
import os
import shutil

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TINY_CONFIG = {
    "name": "tiny", "num_hidden_layers": 2, "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "intermediate_size": 128, "vocab_size": 256,
}


#: Limits for the tiny cells, set by the same rule as the real ones from
#: readings at this size (CPU): sound runs read serve gaps of 0.0017-0.0039,
#: loss gaps under 1e-4, moment gaps 0.003-0.052 and delta gaps under
#: 0.003; the float8 control reads a serve gap of 0.034, moment and delta
#: gaps near 1; half the batch reads moment gaps of 0.47-0.57.
TINY_LIMITS = {
    "serve": {"sample_tokens": 512, "limits": {"serve_gap": 0.012}},
    "finetune": {"limits": {"loss_gap": 5e-4, "moment_gap": 0.2,
                            "delta_gap": 0.01}},
}

#: The window-GQA cell's limit (``WINDOW_GQA`` below), set by the same rule
#: from readings at this size (CPU): sound runs read serve gaps of 0-0.0028
#: over 6 seeds, the float8 control 0.025-0.119.
WINDOW_GQA_LIMITS = {"sample_tokens": 512, "limits": {"serve_gap": 0.012}}


#: A traffic shape the repository's drivers do not have, added as one new
#: driver file: every request due at once (a flash crowd).
CROWD_DRIVER = '''"""Test driver: every request due when the window opens."""
import numpy as np

from bench import gen


class Stream:
    open = True

    def __init__(self, p, vocab, seed):
        rng = np.random.default_rng([seed, 5])
        self.tenants = gen.tenant_names(int(p["tenants"]))
        who = [None] + self.tenants
        self.planned = [
            gen.Planned(0.0, who[i % len(who)],
                        rng.integers(0, vocab, int(p["prompt"]), dtype=np.int32),
                        int(p["output"]))
            for i in range(int(p["requests"]))
        ]


def stream(p, *, vocab, seed, seconds, max_batch):
    return Stream(p, vocab, seed)
'''


#: A model the repository's ``bench/models`` does not have, added as new
#: files only: RMSNorm with no bias, grouped-query attention (4 query heads
#: over 2 KV heads) with a head size of 32 apart from hidden / heads, and a
#: period of a sliding-window (8 keys) and a global attention layer.
WINDOW_GQA = "tiny_window_gqa"

WINDOW_GQA_CONFIG = {
    "name": "tiny-window-gqa", "source": "test", "model": WINDOW_GQA,
    "num_hidden_layers": 4, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32, "intermediate_size": 128,
    "vocab_size": 256, "layer_types": ["attn_local", "attn"],
    "sliding_window": 8, "rope_theta": 10000, "rms_norm_eps": 1e-6,
    "torch_dtype": "bfloat16", "skip_lora_rank": 8,
    "skip_cache_dtype": "float32", "adapt_lr": 0.001,
}

WINDOW_GQA_MODEL = '''"""Test model: RMSNorm with no bias, grouped-query attention with its own
head size, and a period of ``layer_types`` (``attn_local`` attends the
last ``sliding_window`` keys, ``attn`` all of them); gated SiLU FFN,
untied head."""
import functools

import jax
import jax.numpy as jnp

from bench import counts
from bench.weights import NORM_STD, TABLE_STD, jax_key


def program_config(cfg):
    from repro.models.config import ModelConfig

    return ModelConfig(
        name=cfg["name"], family="dense", n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        pattern=tuple(cfg["layer_types"]), sliding_window=cfg["sliding_window"],
        rope_theta=float(cfg["rope_theta"]), rope_pct=1.0,
        ffn_activation="silu", ffn_gated=True, norm_type="rmsnorm",
        norm_eps=cfg["rms_norm_eps"], tie_embeddings=False,
        dtype=cfg["torch_dtype"],
    )


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make_params(key, shape_items, dtype_name):
    n = dict(shape_items)
    dtype = jnp.dtype(dtype_name)
    N, D, H, KV, hd, F, V = (n[k] for k in ("N", "D", "H", "KV", "hd", "F", "V"))
    ks = iter(jax.random.split(key, 64))

    def normal(shape, std):
        return (jax.random.normal(next(ks), shape, jnp.float32) * std).astype(dtype)

    def norm(shape):
        scale = jax.random.normal(next(ks), shape, jnp.float32)
        return {"scale": 1.0 + NORM_STD * scale}

    def layer():
        return {
            "norm1": norm((N, D)),
            "attn": {
                "wq": normal((N, D, H, hd), D ** -0.5),
                "wk": normal((N, D, KV, hd), D ** -0.5),
                "wv": normal((N, D, KV, hd), D ** -0.5),
                "wo": normal((N, H, hd, D), (H * hd) ** -0.5),
            },
            "norm2": norm((N, D)),
            "ffn": {
                "w_gate": normal((N, D, F), D ** -0.5),
                "w_up": normal((N, D, F), D ** -0.5),
                "w_down": normal((N, F, D), F ** -0.5),
            },
        }

    return {
        "embed": {"table": normal((V, D), TABLE_STD)},
        "stack": {"periods": [layer() for _ in range(n["P"])], "remainder": []},
        "final_norm": norm((D,)),
        "head": {"table": normal((V, D), TABLE_STD)},
    }


def make_params(cfg, seed):
    period = len(cfg["layer_types"])
    if cfg["num_hidden_layers"] % period:
        raise ValueError("layers must be whole periods of layer_types")
    dims = {"P": period, "N": cfg["num_hidden_layers"] // period,
            "D": cfg["hidden_size"], "H": cfg["num_attention_heads"],
            "KV": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
            "F": cfg["intermediate_size"], "V": cfg["vocab_size"]}
    return _make_params(jax_key(seed, 1), tuple(sorted(dims.items())),
                        cfg["torch_dtype"])


def _windows(cfg):
    kinds = cfg["layer_types"]
    return [cfg["sliding_window"] if kinds[l % len(kinds)] == "attn_local" else 0
            for l in range(cfg["num_hidden_layers"])]


def matmul_params(cfg):
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    KV, hd, F = cfg["num_key_value_heads"], cfg["head_dim"], cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * (D * (H + 2 * KV) * hd + H * hd * D + 3 * D * F)


def attention_flops(cfg, keys):
    hd = cfg["num_attention_heads"] * cfg["head_dim"]
    return 4.0 * hd * sum(min(keys, w) if w else keys for w in _windows(cfg))


def decode_token_flops(cfg, position):
    return (2.0 * matmul_params(cfg) + attention_flops(cfg, position + 1)
            + counts.skip_flops(cfg) + counts.readout_flops(cfg))


def prefill_flops(cfg, length):
    hd = cfg["num_attention_heads"] * cfg["head_dim"]
    pairs = sum(length * (length + 1) / 2 if not w or w >= length
                else w * (w + 1) / 2 + (length - w) * w for w in _windows(cfg))
    return (2.0 * matmul_params(cfg) * length + 4.0 * hd * pairs
            + counts.skip_flops(cfg) + counts.readout_flops(cfg))
'''

WINDOW_GQA_REFERENCE = '''"""Plain float32 reference of the test model, from its equations: per
layer, a = RMSNorm_1(h); q, k, v with rotary on whole heads; each query
head attends its KV group's keys at positions p - w < t <= p (``w`` the
window of an ``attn_local`` layer, else all); f = RMSNorm_2(h);
h += (silu(f Wg) * (f Wu)) Wd. Imports nothing of the program."""
import functools

import jax
import jax.numpy as jnp

from bench.reference.common import mm, rotary, skip_term


def rms_norm(p, x, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * p["scale"]


def _layer(cfg, precision, window, h, w):
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    t, d = h.shape
    eps = cfg["rms_norm_eps"]
    a = rms_norm(w["norm1"], h, eps)
    at = w["attn"]
    n_h, hd = at["wq"].shape[1], at["wq"].shape[2]
    n_kv = at["wk"].shape[1]
    q = mm(a, at["wq"].reshape(d, -1), precision).reshape(t, n_h, hd)
    k = mm(a, at["wk"].reshape(d, -1), precision).reshape(t, n_kv, hd)
    v = mm(a, at["wv"].reshape(d, -1), precision).reshape(t, n_kv, hd)
    pos = jnp.arange(t)
    theta = cfg["rope_theta"]
    q, k = rotary(q, pos, 1.0, theta), rotary(k, pos, 1.0, theta)
    k, v = jnp.repeat(k, n_h // n_kv, axis=1), jnp.repeat(v, n_h // n_kv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision="highest") / jnp.sqrt(hd)
    keep = pos[:, None] >= pos[None, :]
    if window:
        keep &= pos[:, None] - pos[None, :] < window
    s = jnp.where(keep[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v, precision="highest")
    x_in = h
    h = h + mm(o.reshape(t, -1), at["wo"].reshape(-1, d), precision)
    f = rms_norm(w["norm2"], h, eps)
    ff = w["ffn"]
    u = jax.nn.silu(mm(f, ff["w_gate"], precision)) * mm(f, ff["w_up"], precision)
    return h + mm(u, ff["w_down"], precision), x_in


def hidden(params, cfg, tokens, precision="f32"):
    h = params["embed"]["table"][tokens].astype(jnp.float32)

    def period(h, ws):
        xs = []
        for kind, w in zip(cfg["layer_types"], ws):
            window = cfg["sliding_window"] if kind == "attn_local" else 0
            h, x = _layer(cfg, precision, window, h, w)
            xs.append(x)
        return h, jnp.stack(xs)

    h, xs = jax.lax.scan(period, h, params["stack"]["periods"])
    return h, xs.reshape((-1,) + xs.shape[2:])


def logits(params, cfg, y, precision="f32"):
    yn = rms_norm(params["final_norm"], y, cfg["rms_norm_eps"])
    return mm(yn, params["head"]["table"].astype(jnp.float32).T, precision)


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision"))
def _sequence_logits(params, tokens, a, b, *, cfg_items, precision):
    cfg = dict(cfg_items)
    h, xs = hidden(params, cfg, tokens, precision)
    return logits(params, cfg, h + skip_term(xs, a, b, precision), precision)


def cfg_items(cfg):
    return (("layer_types", tuple(cfg["layer_types"])),
            ("rms_norm_eps", cfg["rms_norm_eps"]),
            ("rope_theta", cfg["rope_theta"]),
            ("sliding_window", cfg["sliding_window"]))


def sequence_logits(params, cfg, tokens, a, b, precision="f32"):
    return _sequence_logits(params, tokens, a, b, cfg_items=cfg_items(cfg),
                            precision=precision)
'''


def shrink(mix: dict) -> dict:
    mix = copy.deepcopy(mix)
    if "serve" in mix:
        sv, sc = mix["serve"], mix["scheduler"]
        for k in ("prompt_len", "output_len", "first_wave_output_len"):
            for q in ("median", "min", "max", "value"):
                if q in sv.get(k, {}):
                    sv[k][q] = max(1, sv[k][q] // 16)
        sc.update(max_prompt=sc["max_prompt"] // 16,
                  max_new_cap=sc["max_new_cap"] // 16, max_batch=4)
        sv["tenants"] = 6
        if sv["loop"] == "open":
            sv["rate_per_s"] = 4.0
    if "finetune" in mix:
        mix["finetune"].update(cohort=2, samples=4, seq=8, ingest_batch=2,
                               epochs=3, batch_per_tenant=2)
    return mix


def make_root(dst: str, base_config: str = "stablelm-1.6b") -> dict:
    """Copy ``bench`` into ``dst`` and add a ``tiny-<cell>`` cell for every
    cell, on a tiny configuration. Returns the new BENCHMARK.json."""
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    with open(os.path.join(ROOT, "bench", "configs", f"{base_config}.json")) as f:
        cfg = json.load(f)
    cfg.update(TINY_CONFIG)
    with open(os.path.join(dst, "bench", "configs", "tiny.json"), "w") as f:
        json.dump(cfg, f)
    spec["configs"].append({"name": "tiny", "source": "test",
                            "file": "bench/configs/tiny.json", "reduced": [],
                            "why": "test"})
    for w in list(spec["workloads"]):
        with open(os.path.join(ROOT, "bench", "traffic", f"{w['traffic']}.json")) as f:
            mix = shrink(json.load(f))
        name = f"tiny-{w['name']}"
        with open(os.path.join(dst, "bench", "traffic", f"{name}.json"), "w") as f:
            json.dump(mix, f)
        with open(os.path.join(dst, "bench", "limits", f"{name}.json"), "w") as f:
            json.dump(TINY_LIMITS["serve" if "serve" in mix else "finetune"], f)
        spec["workloads"].append({"name": name, "config": "tiny",
                                  "traffic": name, "chips": 1, "why": "test"})
        for m in spec["end_to_end"] + spec["per_layer"]:
            if w["name"] in m.get("workloads", []):
                m["workloads"].append(name)
    # Three more cells: chat's mix with bursts and shared prefixes
    # (parameters only), a flash crowd from a new driver file, and chat's
    # requests beside fine-tune jobs (two parts of one mix).
    def read(kind, name):
        with open(os.path.join(dst, "bench", kind, f"tiny-{name}.json")) as f:
            return json.load(f)

    chat, jobs = read("traffic", "stablelm-chat"), read("traffic", "musicgen-finetune")
    bursty = copy.deepcopy(chat)
    bursty["serve"].update(
        bursts={"factor": 4, "seconds": 0.5, "every": 1.0},
        shared_prefix={"len": {"dist": "uniform", "min": 8, "max": 16}})
    bursty["scheduler"]["max_prompt"] += 16
    crowd = {"serve": {"driver": "tiny_crowd", "tenants": 3, "requests": 6,
                       "prompt": 8, "output": 16},
             "scheduler": chat["scheduler"]}
    both = dict(chat, finetune=jobs["finetune"])
    limits = {**TINY_LIMITS["serve"], "limits": {
        **TINY_LIMITS["serve"]["limits"], **TINY_LIMITS["finetune"]["limits"]}}
    with open(os.path.join(dst, "bench", "traffic", "tiny_crowd.py"), "w") as f:
        f.write(CROWD_DRIVER)
    for name, mix, like in (
        ("tiny-chat-bursty", bursty, ["tiny-stablelm-chat"]),
        ("tiny-crowd", crowd, ["tiny-stablelm-chat"]),
        ("tiny-serve-adapt", both, ["tiny-stablelm-chat", "tiny-musicgen-finetune"]),
    ):
        with open(os.path.join(dst, "bench", "traffic", f"{name}.json"), "w") as f:
            json.dump(mix, f)
        with open(os.path.join(dst, "bench", "limits", f"{name}.json"), "w") as f:
            json.dump(limits if "finetune" in mix else TINY_LIMITS["serve"], f)
        spec["workloads"].append({"name": name, "config": "tiny",
                                  "traffic": name, "chips": 1, "why": "test"})
        for m in spec["end_to_end"] + spec["per_layer"]:
            if any(c in m.get("workloads", []) for c in like):
                m["workloads"].append(name)
    # A cell on a model of its own: chat's tiny mix on the window-GQA model,
    # whose module and reference are new files under bench/models and
    # bench/reference.
    for kind, text in (("models", WINDOW_GQA_MODEL),
                       ("reference", WINDOW_GQA_REFERENCE)):
        with open(os.path.join(dst, "bench", kind, f"{WINDOW_GQA}.py"), "w") as f:
            f.write(text)
    with open(os.path.join(dst, "bench", "configs", "tiny-window-gqa.json"), "w") as f:
        json.dump(WINDOW_GQA_CONFIG, f)
    spec["configs"].append({"name": "tiny-window-gqa", "source": "test",
                            "file": "bench/configs/tiny-window-gqa.json",
                            "reduced": [], "why": "test"})
    name = "tiny-window-gqa-chat"
    with open(os.path.join(dst, "bench", "traffic", f"{name}.json"), "w") as f:
        json.dump(chat, f)
    with open(os.path.join(dst, "bench", "limits", f"{name}.json"), "w") as f:
        json.dump(WINDOW_GQA_LIMITS, f)
    spec["workloads"].append({"name": name, "config": "tiny-window-gqa",
                              "traffic": name, "chips": 1, "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "tiny-stablelm-chat" in m.get("workloads", []):
            m["workloads"].append(name)
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return spec
