"""Operations of a DeepSeek-V2 training step on one chip's share of its
experts, from the configuration's published widths alone.

The share is ``n_routed_experts`` held experts of the router's
``deployment.router_experts``; a token is routed to
``num_experts_per_tok`` of all of them, so on average ``k * held /
router_experts`` of its assignments land here.  Model FLOPs count 6 per
active matmul parameter per token (forward and backward; the embedding
lookup is no matmul; recomputation not counted) plus causal attention:
per token and layer, the scores over qk width and the values over v width
for half of the sequence, three times (forward and backward).
"""

from __future__ import annotations


def mla_params(c: dict) -> int:
  d, h = c["hidden_size"], c["num_attention_heads"]
  nd, rd, vd = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
  r = c["kv_lora_rank"]
  return d * h * (nd + rd) + d * (r + rd) + r * h * (nd + vd) + h * vd * d


def expert_params(c: dict) -> int:
  """One SiLU-gated expert: gate, up and down."""
  return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def active_params(c: dict) -> float:
  """Matmul parameters one token is multiplied by, on average, here."""
  d, e = c["hidden_size"], c["deployment"]["router_experts"]
  dense = c["first_k_dense_replace"]
  held_per_token = c["num_experts_per_tok"] * c["n_routed_experts"] / e
  moe_layer = (mla_params(c) + d * e
               + (c["n_shared_experts"] + held_per_token) * expert_params(c))
  return (dense * (mla_params(c) + 3 * d * c["intermediate_size"])
          + (c["num_hidden_layers"] - dense) * moe_layer
          + d * c["vocab_size"])


def train_flops(c: dict, batch: int, seq: int) -> float:
  """Model FLOPs of one forward and backward pass over batch x seq."""
  tokens = batch * seq
  width = (c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"])
  attn = 3.0 * c["num_hidden_layers"] * seq * c["num_attention_heads"] * width
  return tokens * (6.0 * active_params(c) + attn)


def expert_flops_per_assignment(c: dict) -> float:
  """Forward and backward FLOPs of one token through one expert."""
  return 6.0 * expert_params(c)


def router_entries(c: dict, batch: int, seq: int) -> int:
  """Entries of the router's soft top-k rows in one step: one row of all
  the router's experts per token and expert layer."""
  return (batch * seq * c["deployment"]["router_experts"]
          * (c["num_hidden_layers"] - c["first_k_dense_replace"]))
