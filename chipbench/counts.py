"""Operations and bytes from shapes alone, never from the implementation.

``soft_op_bytes``: the least HBM traffic of one soft operator call,
forward and backward: the forward reads its input and writes its output,
the backward reads the output's cotangent and writes the input's, four
float32 arrays of the call's shape.  Everything else the program moves
(sorts, permutations, the solver's levels) is above this floor.

``train_flops``: the model FLOPs of one training step, 6 per active
matmul parameter per token (forward and backward) plus causal attention,
with recomputation not counted.  The active-parameter count follows
``repro.analysis.roofline.count_active_params`` (the embedding lookup is
no matmul; norms and biases are not counted), worked out from the
configuration's published widths.
"""

from __future__ import annotations

F32_BYTES = 4


def soft_op_bytes(entries: int) -> float:
  """Least HBM bytes of a soft operator's forward and backward over
  ``entries`` float32 values."""
  return 4.0 * F32_BYTES * entries


def matmul_params(config: dict) -> int:
  """Active matmul parameters of a dense decoder, from its config."""
  d = config["hidden_size"]
  heads = config["num_attention_heads"]
  kv = config["num_key_value_heads"]
  head_dim = d // heads
  f = config["intermediate_size"]
  gated = 3 if config["hidden_act"] == "silu" else 2
  per_layer = (d * heads * head_dim * 2          # q, o
               + d * kv * head_dim * 2           # k, v
               + gated * d * f)                  # gate, up, down
  head = 0 if config["tie_word_embeddings"] else d * config["vocab_size"]
  return config["num_hidden_layers"] * per_layer + head


def train_flops(config: dict, batch: int, seq: int) -> float:
  """Model FLOPs of one forward and backward pass over batch x seq
  tokens: 6 N per token, plus 6 * layers * seq * attention width per
  token for the causal scores and values (half of the full square)."""
  tokens = batch * seq
  attn = (6.0 * config["num_hidden_layers"] * seq
          * config["hidden_size"] * tokens)
  return 6.0 * matmul_params(config) * tokens + attn
