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
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return spec
