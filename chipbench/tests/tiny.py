"""Tiny cells of each family for the CPU tests."""

from __future__ import annotations

import copy
import os

from chipbench import harness

OPT = {"name": "adamw", "lr": 1e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
       "weight_decay": 0.1, "clip_global_norm": 1.0, "moments": "float32"}

MODELS = {
    "ssm": {"num_layers": 2, "d_model": 64, "num_heads": 8,
            "num_kv_heads": 8, "d_ff": 0, "vocab_size": 300,
            "rms_eps": 1e-5, "tie_embeddings": True,
            "ssm": {"d_state": 16, "d_conv": 4, "expand": 2, "headdim": 16,
                    "chunk": 16}},
    "dense": {"num_layers": 2, "d_model": 64, "num_heads": 4,
              "num_kv_heads": 2, "head_dim": 16, "d_ff": 128,
              "vocab_size": 300, "qk_norm": True, "rope_theta": 1e6,
              "rms_eps": 1e-6, "tie_embeddings": True},
}


def config(fam: str, layers: int = 2) -> dict:
    model = dict(copy.deepcopy(MODELS[fam]), num_layers=layers)
    return {"name": f"tiny-{fam}", "family": fam, "dtype": "bfloat16",
            "optimizer": dict(OPT), "model": model}


def cell(fam: str, *, chips: int = 1, clients_per_chip: int = 2,
         seq_len: int = 32, fuse="flat", limits=None,
         layers: int = 2) -> harness.Cell:
    bench = harness._read_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    traffic = {"clients_per_chip": clients_per_chip, "batch": 1,
               "seq_len": seq_len, "ngram_dependency": 0.7, "fuse": fuse,
               "codec": None, "churn": None}
    return harness.Cell(
        name=f"tiny-{fam}", chips=chips, config=config(fam, layers),
        traffic=traffic,
        limits=limits, end_to_end=[m["name"] for m in bench["end_to_end"]],
        per_layer=[],
        units={m["name"]: m["unit"]
               for m in bench["end_to_end"] + bench["per_layer"]})
