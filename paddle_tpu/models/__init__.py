"""In-repo model zoo (BASELINE.json configs).

- llama: Llama-2 family (7B/13B/70B + small configs) — flagship
- gpt: GPT/ERNIE-style decoder (13B TP+PP config)
- moe: Mixtral-style mixture-of-experts (expert parallel)
- sdxl_unet: Stable-Diffusion-XL UNet (conv/GroupNorm/attention breadth)
- evabyte: EvaByte, a byte-level LM on EVA chunked linearized attention
  (window pages beside chunk summaries in the serving cache)
- qwen3_next: Qwen3-Next, gated-delta linear-attention layers beside gated
  softmax attention, dropless top-k experts with a shared expert (trains;
  serving needs a cache kind that is not there yet)
"""

from .llama import (LlamaConfig, LlamaForCausalLM, LlamaModel, PRESETS,  # noqa: F401
                    causal_lm_loss, llama)


def __getattr__(name):
    import importlib
    if name in ("gpt", "moe", "sdxl_unet", "evabyte", "qwen3_next"):
        return importlib.import_module(f".{name}", __name__)
    raise AttributeError(name)
