#!/usr/bin/env python3
"""Entry point of the serving child: the program's own `main`, started
under a configuration the benchmark describes in a data file.

    python cellbench/harness/serve.py --config cellbench/configs/<name>.json \
        --trace-dir <dir> -- <the program's own flags>

The program takes a registry name and has no depth flag, and the benchmark
may not edit `models/registry.py`; so a configuration file names its `base`
registry entry and its `overrides`, and this file registers
`base.replace(name=<name>, **overrides)` before calling the program's
`main(argv)`. It also checks the registered sizes against the published
ones in the file, so a file that says one thing and a registry that says
another never runs.

One more thing is set here, and nothing else of the program is wrapped:
`POST /profiler/start` writes under a base directory fixed in code
(`/tmp/jax-traces`), which two checkouts on one machine would share; the
base becomes `--trace-dir`, inside this checkout (PERF.md, Open questions:
the program wants a flag for it).
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# published key (HF config.json) -> the registry's field
PUBLISHED_TO_REGISTRY = {
    "hidden_size": "dim",
    "intermediate_size": "ffn_dim",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "vocab_size": "vocab_size",
    "max_position_embeddings": "max_seq_len",
    "sliding_window": "attn_window",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
}

ENTRIES = {
    "server": "distributed_llm_inference_tpu.serving.server",
    "router": "distributed_llm_inference_tpu.serving.router",
}


def register_config(config: dict):
    """Register the configuration under its own name; returns the entry."""
    from distributed_llm_inference_tpu.models.registry import (
        get_model_config,
        register,
    )

    serving = config["serving"]
    cfg = get_model_config(serving["base"])
    if serving.get("overrides") or config["name"] != serving["base"]:
        cfg = register(cfg.replace(name=config["name"], **serving.get("overrides", {})))
    for key, field in PUBLISHED_TO_REGISTRY.items():
        if key in config and getattr(cfg, field) != config[key]:
            raise SystemExit(
                f"{config['name']}: the file says {key}={config[key]!r}, the "
                f"registry runs {field}={getattr(cfg, field)!r}"
            )
    if "head_dim" in config and cfg.head_dim != config["head_dim"]:
        raise SystemExit(
            f"{config['name']}: head_dim {config['head_dim']} != {cfg.head_dim}"
        )
    return cfg


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--trace-dir", required=True)
    ap.add_argument("argv", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    argv = args.argv[1:] if args.argv[:1] == ["--"] else args.argv
    sys.path.insert(0, ROOT)
    with open(args.config) as f:
        config = json.load(f)
    register_config(config)
    if config["serving"].get("entry") == "router" and (
        config["serving"].get("overrides") or config["name"] != config["serving"]["base"]
    ):
        raise SystemExit(
            "a router spawns plain `serving.server` replicas, which know only "
            "the registry's own names: a configuration behind a router may "
            "not carry overrides"
        )
    from distributed_llm_inference_tpu.serving import server

    server._Profiler.__init__.__defaults__ = (os.path.abspath(args.trace_dir),)
    entry = importlib.import_module(ENTRIES[config["serving"].get("entry", "server")])
    entry.main(argv)


if __name__ == "__main__":
    main()
