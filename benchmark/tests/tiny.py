"""A tiny copy of the benchmark for CPU rehearsals: the same manifest,
runners, readers and metric files, with configurations and traffic cut to
sizes a test can hold.  It is built in a temporary root as new files only,
which is also how a later PR adds a configuration or a mix."""
from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}

TINY_LM = {"hidden_size": 64, "intermediate_size": 128,
           "num_attention_heads": 4, "num_key_value_heads": 2,
           "head_dim": 16, "vocab_size": 512, "sliding_window": 48,
           "num_hidden_layers": 2, "rope_theta": 10000.0}


# Read on the CPU at these sizes (bf16 program, fp8 control, 4 seeds):
# losses: program <= 7e-4; gradient norm: program <= 2.3e-3, control
# >= 0.21; parameters' change: program <= 1.9e-3, control >= 0.024.
TINY_LIMITS = {"loss_rel": 2.5e-3, "grad_norm_rel": 0.02,
               "delta_norm_rel": 0.008, "logit_gap": 0.02}


# Eight 32 px images: batch norm over a handful of values makes bf16
# wander far (losses to 0.14, kernels 0.07 on the CPU).
TINY_RESNET_LIMITS = {
    "loss_rel": 0.5, "grad_norm_rel": {"kernel": 0.3},
    "delta_norm_rel": {"kernel": 0.3}}


def _load(path):
    with open(path) as f:
        return json.load(f)


def make_root(tmp: str) -> str:
    """Write the tiny benchmark under `tmp` and return that root."""
    manifest = _load(os.path.join(ROOT, "BENCHMARK.json"))
    manifest["paths"] = ["bench"]
    bench = os.path.join(tmp, "bench")
    for d in ("configs", "traffic"):
        os.makedirs(os.path.join(bench, d))
    shutil.copytree(os.path.join(BENCH, "metrics"),
                    os.path.join(bench, "metrics"))
    for entry in manifest["configs"]:
        cfg = _load(os.path.join(ROOT, entry["file"]))
        if cfg["family"] == "decoder_lm":
            cfg.update(TINY_LM)
            cfg["limits"] = TINY_LIMITS
            if "serve" in cfg:
                cfg["serve"].update(page_tokens=4, check_requests=8)
        else:
            cfg.update(image_size=32, num_classes=10)
            cfg["limits"].update(TINY_RESNET_LIMITS)
        entry["file"] = f"bench/configs/{entry['name']}.json"
        with open(os.path.join(tmp, entry["file"]), "w") as f:
            json.dump(cfg, f)
    for cell in manifest["workloads"]:
        tr = _load(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))
        if tr["kind"] == "batches":
            tr.update(per_chip_batch=2, resident_batches=2)
            if "seq_len" in tr:
                tr["seq_len"] = 32
        else:
            for p in tr["pairs"]:
                p["prompt"] = max(2, p["prompt"] // 128)
                p["output"] = max(2, p["output"] // 32)
            tr["ramp"].update(warm_pair={"prompt": 2, "output": 2},
                              max_group=2)
            tr["server"].update(max_batch=3, max_seq_tokens=48)
            tr["server"].pop("pool_pages", None)
            if tr["arrivals"]["process"] == "open":
                tr["arrivals"].update(rate_per_s=20.0, horizon_s=8.0)
            else:
                tr["arrivals"]["requests"] = 4000
            tr["ramp"].update(settle_steps=4, stagger_steps=2)
        with open(os.path.join(bench, "traffic",
                               cell["traffic"] + ".json"), "w") as f:
            json.dump(tr, f)
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return tmp
