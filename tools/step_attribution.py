#!/usr/bin/env python
"""Attribute the llama-350m train step to op classes by ABLATION of the
real compiled step (VERDICT r3 weak #4 / directive #7).

Isolated-op grad microbenches are structurally untrustworthy here: with
any fixed cotangent XLA algebraically folds `sum((x@w)·p)` into the same
matmul as dx and CSEs them (we measured impossible >100%-of-peak
numbers).  Instead each class is removed from the REAL model (forward
patched to identity / cheap stand-in), the full TrainStep is recompiled,
and the class is charged the step-time delta.  Interactions (fusion
across class boundaries) land in the printed residual instead of being
silently mis-attributed.

Classes ablated:
  attn_core  F.scaled_dot_product_attention → v   (flash fwd+bwd)
  qkvo+rope  LlamaAttention.forward → x           (minus attn_core)
  mlp        LlamaMLP.forward → x
  norms      LlamaRMSNorm.forward → x
  head+CE    CausalLM loss path → hidden.mean()
  rope       F.apply_rotary_pos_emb → (q, k)

Usage: python tools/step_attribution.py [--preset llama-350m]
       [--steps 20] [--windows 2]
Prints a markdown table for docs/BENCH.md + one JSON line.
"""

import argparse
import contextlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax.numpy as jnp


@contextlib.contextmanager
def patched(obj, name, repl):
    orig = getattr(obj, name)
    setattr(obj, name, repl)
    try:
        yield
    finally:
        setattr(obj, name, orig)


def run(preset, steps, windows, batch=4, seq=2048):
    import bench
    _, stats = bench.measure(preset, batch, seq, steps, windows)
    return stats["ms_per_step"]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="llama-350m")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--windows", type=int, default=2)
    args = ap.parse_args()

    import importlib

    M = importlib.import_module("paddle_tpu.models.llama")
    from paddle_tpu.nn import functional as F

    steps, windows = args.steps, args.windows
    results = {}

    results["baseline"] = run(args.preset, steps, windows)

    with patched(F, "scaled_dot_product_attention",
                 lambda q, k, v, *a, **kw: v):
        results["no_attn_core"] = run(args.preset, steps, windows)

    with patched(M.LlamaAttention, "forward",
                 lambda self, x, cos, sin, attn_mask=None, cache=None,
                 seq_lens=None: x):
        results["no_attention_block"] = run(args.preset, steps, windows)

    with patched(M.LlamaMLP, "forward", lambda self, x: x):
        results["no_mlp"] = run(args.preset, steps, windows)

    with patched(M.LlamaRMSNorm, "forward", lambda self, x: x):
        results["no_norms"] = run(args.preset, steps, windows)

    with patched(F, "apply_rotary_pos_emb",
                 lambda q, k, cos, sin, *a, **kw: (q, k)):
        results["no_rope"] = run(args.preset, steps, windows)

    orig_fwd = M.LlamaForCausalLM.forward

    def pooled_loss_fwd(self, input_ids, labels=None, attn_mask=None,
                        position_ids=None):
        hidden = self.model(input_ids, attn_mask, position_ids)
        if labels is None:
            return orig_fwd(self, input_ids, labels, attn_mask,
                            position_ids)
        return jnp.mean(hidden.astype(jnp.float32))

    with patched(M.LlamaForCausalLM, "forward", pooled_loss_fwd):
        results["no_head_ce"] = run(args.preset, steps, windows)

    base = results["baseline"]
    attr = {
        "attention core (flash fwd+bwd)": base - results["no_attn_core"],
        "qkvo proj + rope + layouts": results["no_attn_core"]
        - results["no_attention_block"],
        "mlp (gate/up/down + swiglu)": base - results["no_mlp"],
        "rmsnorm (x2/layer)": base - results["no_norms"],
        "rope": base - results["no_rope"],
        "embed+lmhead+CE": base - results["no_head_ce"],
    }
    accounted = (attr["attention core (flash fwd+bwd)"]
                 + attr["qkvo proj + rope + layouts"]
                 + attr["mlp (gate/up/down + swiglu)"]
                 + attr["rmsnorm (x2/layer)"]
                 + attr["embed+lmhead+CE"])
    residual = base - accounted

    print(f"\nbaseline step: {base:.1f} ms  (preset {args.preset}, "
          f"bs4 x 2048, steps={steps} x windows={windows})\n")
    print("| class | ms/step | share | ablation |")
    print("|---|---|---|---|")
    rows = [
        ("attention core (flash fwd+bwd)", "sdpa → v"),
        ("qkvo proj + rope + layouts", "attn block → x, minus core"),
        ("mlp (gate/up/down + swiglu)", "mlp → x"),
        ("rmsnorm (x2/layer)", "norm → x"),
        ("rope", "rotary → identity (subset of qkvo row)"),
        ("embed+lmhead+CE", "loss → mean(hidden)"),
    ]
    for name, note in rows:
        v = attr[name]
        print(f"| {name} | {v:.1f} | {v / base:.0%} | {note} |")
    print(f"| interaction residual | {residual:.1f} | "
          f"{residual / base:.0%} | fusion across class boundaries |")
    print()
    print(json.dumps({"baseline_ms": base, "raw": results,
                      "attribution_ms": {k: round(v, 1)
                                         for k, v in attr.items()},
                      "residual_ms": round(residual, 1)}))


if __name__ == "__main__":
    main()
