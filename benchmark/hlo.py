"""Readers of a compiled program's text (``pallas_kernels`` copied from
chip_smoke.py, which stays the program's start-up proof; PERF.md section 7
lists the original for a later PR to point here)."""

from __future__ import annotations

import collections
import re


def pallas_kernels(hlo: str) -> dict:
    """Name -> count of the Pallas (Mosaic) custom calls in a compiled
    program's text; the kernels carry stable names (ops/pallas)."""
    names = re.findall(        # under autodiff: transpose(jvp(<name>))
        r'custom_call_target="tpu_custom_call"[^\n]*?'
        r'op_name="[^"]*?/(?:\w+\()*(\w+)\)*/pallas_call', hlo)
    return dict(collections.Counter(names))


def instruction_scopes(hlo: str) -> dict:
    """HLO instruction name -> its ``op_name`` metadata (the jax scope
    path, which holds a Pallas kernel's name).  The device trace names an
    event by its instruction; the metric files' patterns name the work."""
    out = {}
    for m in re.finditer(
            r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = [^\n]*?op_name="([^"]*)"',
            hlo, flags=re.M):
        out[m.group(1)] = m.group(2)
    return out
