"""The DeepSeek-V2 configuration cut to a size a CPU test can hold: the
same structure (a leading dense layer, two expert layers holding 2 of 8
experts under top-2 routing with one shared expert, YaRN), small widths."""

from __future__ import annotations

import json
import os

from chipbench import harness

FILE = os.path.join(harness.HERE, "configs", "deepseek-v2-lite-ep8.json")


def shrink_config(c: dict) -> None:
  c.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
           qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
           kv_lora_rank=32, intermediate_size=128, moe_intermediate_size=32,
           n_routed_experts=2, num_experts_per_tok=2, n_shared_experts=1,
           num_hidden_layers=3, vocab_size=256)
  c["deployment"] = dict(c["deployment"], router_experts=8,
                         first_held_expert=2)
  c["program"]["replace"].update(
      d_model=64, num_heads=4, num_kv_heads=4, head_dim=24, qk_nope_dim=16,
      qk_rope_dim=8, v_head_dim=16, kv_lora_rank=32, d_ff=128, moe_d_ff=32,
      num_experts=8, experts_held=2, first_held_expert=2,
      experts_per_token=2, num_shared_experts=1, num_layers=3,
      vocab_size=256, q_chunk=32, kv_chunk=32, xent_chunk=32)
  c["train"].update(batch=2, seq=64)


def small_config() -> dict:
  with open(FILE) as f:
    c = json.load(f)
  shrink_config(c)
  return c


def shrink(ctx) -> None:
  """``chipbench.run.main``'s ``tweak`` for the cell."""
  shrink_config(ctx.config)
  # This width's own readings on the CPU: sound runs (13 seeds) read loss
  # 7.2e-4, gradient 0.029 and update 0.036 at most; half of the batch
  # left out (2 seeds) reads 0.015, 0.17 and 0.068 at least.
  ctx.traffic["limits"] = {"loss_gap": 1e-3, "grad_gap": 0.05,
                           "update_gap": 0.05}
