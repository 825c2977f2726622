"""Plain references, one module per model family.

A configuration file names its family's module under ``reference``; that
module is the family's whole description on the benchmark side, and no other
file of the harness knows a family.  It exports:

- ``forward(params, config, tokens, fp8=False)``: float32 logits (S, V) of
  one sequence, and with ``fp8`` the correctness control;
- ``shapes(config) -> {path: (shape, std)}``: the weight tree it reads, in
  the program's layout (``weights.make``, ``weights.check_layout``);
- ``program_fields(config) -> dict``: keyword arguments for the program's
  ``get_config(program_model).replace(...)``; a dict value replaces fields
  of the nested group of that name (``{"moe": {"n_experts": 8}}``);
- ``matmul_flops_per_token(config)``: twice the weights each token
  multiplies by (for a MoE, the active experts');
- ``step_weight_bytes(config, rows)``: bytes of weights a decode step of
  ``rows`` live rows must read;
- ``attention_work(config, kv_len) -> (flops, bytes)``: what one query token
  at live length ``kv_len`` costs over all layers.

The mathematics imports nothing of the program and reads only the weights
that ``weights.make`` drew from the seed.
"""
from __future__ import annotations

import importlib


def family(config: dict):
    """The reference module that a configuration names."""
    return importlib.import_module(f"{__name__}.{config['reference']}")
