"""A throwaway smoke-size cell for the benchmark's own CPU tests.

:func:`make_tree` copies ``BENCHMARK.json`` and ``bench/`` into a temporary
checkout and adds a cell of its own there, with new files only, the way a
later change adds a cell: a smoke-size dense model served open-loop.
:func:`run_cell` drives ``bench/run.py``'s main on that tree without the
chip check.
"""

from __future__ import annotations

import contextlib
import io
import json
import shutil
from pathlib import Path
from typing import Dict, Optional

REPO = Path(__file__).resolve().parents[2]

SMOKE_LM = {
    "name": "smollm-smoke", "source": "smoke size of smollm-135m",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
    "vocab_size": 512, "max_position_embeddings": 128, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-06, "tie_word_embeddings": True, "hidden_act": "silu",
    "embedding_multiplier": 8.0, "reduced": [],
    "dtypes": {"params": "float32", "compute": "bfloat16",
               "kv_cache": "bfloat16"},
    "serving": {"slots": 4, "max_seq": 128, "prefill_bucket": 8},
}

SMOKE_CHAT = {
    "config": "smollm-smoke", "traffic": "smoke-chat",
    "driver": "serve_openloop",
    "mix": {"arrivals": "poisson", "rate_rps": 6.0,
            "prompt_len": {"dist": "lognormal", "median": 20, "sigma": 0.6,
                           "min": 4, "max": 60},
            "output_len": {"dist": "uniform", "min": 4, "max": 24},
            "preroll_s": 0.5, "after_s": 10.0, "drain_cap_s": 30.0},
    "warm_buckets": [8, 16, 32, 64], "trace": {"seconds": 1.0},
    "check": {"sample_requests": 3, "limits": {"max_logit_gap": 0.05}},
}

def make_tree(dest: Path, lm: Optional[Dict] = None,
              chat: Optional[Dict] = None) -> Path:
    """A checkout with the repo's benchmark plus the smoke cell
    ``smollm-smoke.smoke-chat``."""
    shutil.copytree(REPO / "bench", dest / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    lm = lm or SMOKE_LM
    (dest / "bench/configs/smollm-smoke.json").write_text(json.dumps(lm))
    spec["configs"].append({"name": "smollm-smoke", "source": lm["source"],
                            "file": "bench/configs/smollm-smoke.json",
                            "reduced": [], "why": "smoke"})
    name, doc = "smollm-smoke.smoke-chat", chat or SMOKE_CHAT
    (dest / f"bench/workloads/{name}.json").write_text(json.dumps(doc))
    spec["workloads"].append({"name": name, "config": doc["config"],
                              "traffic": doc["traffic"], "chips": 1,
                              "why": "smoke"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(name)
    (dest / "BENCHMARK.json").write_text(json.dumps(spec))
    return dest


def run_cell(root: Path, cell: str, seed: int = 7, seconds: float = 2.0,
             trace: int = 0):
    """Run one cell of ``root`` on the CPU; returns (exit code, result
    dict or None, stdout, stderr)."""
    from bench import run
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = run.main(["--workload", cell, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)],
                      allow_cpu=True, root=root)
    lines = out.getvalue().strip().splitlines()
    result = None
    if rc == 0 and lines:
        result = json.loads(lines[-1])
    return rc, result, out.getvalue(), err.getvalue()
