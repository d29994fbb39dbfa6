"""Architecture configuration schema + registry.

One ``ArchConfig`` instance per assigned architecture (exact numbers from the
assignment table) plus reduced "smoke" variants of the same family for CPU
tests.  ``layer_kinds()`` expands the block-pattern cycle into a per-layer
kind list; ``plan_segments()`` groups it into scannable segments.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

_REGISTRY: dict[str, "ArchConfig"] = {}

# The dense layer kind that leads a cycle of this kind.
_DENSE_TWIN = {"moe": "dense", "mla_moe": "mla_dense"}


@dataclasses.dataclass(frozen=True)
class Yarn:
  """YaRN rope scaling, as a HF ``rope_scaling`` of type ``yarn`` holds
  it (DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding``)."""
  factor: float
  original_max_position: int
  beta_fast: float = 32.0
  beta_slow: float = 1.0
  mscale: float = 1.0
  mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class ArchConfig:
  name: str
  family: str                     # dense|moe|vlm|hybrid|ssm|audio
  num_layers: int
  d_model: int
  num_heads: int
  num_kv_heads: int
  head_dim: int
  d_ff: int
  vocab_size: int

  # Block pattern: cycle of layer kinds, applied as kind[i % len(cycle)]
  # after ``first_dense_layers`` leading layers of the cycle's dense twin
  # (moe -> dense, mla_moe -> mla_dense).
  # Kinds: dense | global | local | moe | mla_dense | mla_moe | rg |
  #        mlstm | slstm
  block_cycle: tuple[str, ...] = ("dense",)
  first_dense_layers: int = 0     # deepseek's first_k_dense_replace
  window_size: int = 0            # sliding window for "local" layers

  # MoE
  num_experts: int = 0
  experts_per_token: int = 0
  num_shared_experts: int = 0
  moe_d_ff: int = 0
  router: str = "softmax_topk"    # softmax_topk | soft_topk (paper)
  router_eps: float = 1.0
  norm_topk_prob: bool = True     # renormalise the chosen experts' weights
  # This chip's share of the experts: ``experts_held`` of them (0 = all),
  # ids first_held_expert .. first_held_expert + experts_held - 1.
  experts_held: int = 0
  first_held_expert: int = 0

  # MLA (deepseek)
  kv_lora_rank: int = 0
  qk_nope_dim: int = 0
  qk_rope_dim: int = 0
  v_head_dim: int = 0

  # Recurrent (RG-LRU)
  lru_width: int = 0
  conv_width: int = 4

  # MLP / norm / embeddings
  mlp_variant: str = "swiglu"     # swiglu | geglu | gelu
  norm: str = "rmsnorm"           # rmsnorm | layernorm
  rope_theta: float = 10000.0
  rope_yarn: Yarn | None = None   # YaRN rope scaling (deepseek)
  tie_embeddings: bool = False
  logit_softcap: float = 0.0

  # Modality frontend stub
  frontend: str = "none"          # none | vision | audio
  num_codebooks: int = 0          # audio: parallel output heads
  num_patches: int = 0            # vision: patch-embedding prefix length

  # Numerics / training-step shape
  dtype: str = "bfloat16"
  remat: str = "full"             # none | dots | full
  grad_accum: int = 1
  grad_accum_dtype: str = "float32"  # bf16 for param-bound giants (grok)
  xent_chunk: int = 1024          # sequence chunking for the LM-head loss
  q_chunk: int = 512              # flash-attention query block
  kv_chunk: int = 1024            # flash-attention kv block

  # Paper-technique knobs
  loss_trim_fraction: float = 0.0   # soft-LTS token trimming (0 = off)
  loss_trim_eps: float = 1e-2

  # Sharding strategy
  fsdp: bool = False              # also shard weights/opt-state over data
  seq_shard_activations: bool = False
  supports_long_context: bool = False  # run long_500k? (sub-quadratic)

  @property
  def attn_dim(self) -> int:
    return self.num_heads * self.head_dim

  @property
  def num_experts_held(self) -> int:
    return self.experts_held or self.num_experts

  def layer_kinds(self) -> list[str]:
    cyc = self.block_cycle
    lead = ([_DENSE_TWIN[cyc[0]]] * self.first_dense_layers
            if self.first_dense_layers else [])
    return lead + [cyc[i % len(cyc)]
                   for i in range(self.num_layers - len(lead))]

  def plan_segments(self) -> list[tuple[tuple[str, ...], int]]:
    """Group layers into (cycle, repeats) segments for lax.scan stacking.

    Leading dense layers form the first segment.  The full cycle is then
    scanned ``(num_layers - first_dense_layers) // len(cycle)`` times; any
    remainder layers form a trailing unrolled segment (repeats=1 each
    sub-cycle so params still stack uniformly).
    """
    kinds = self.layer_kinds()
    segments: list[tuple[tuple[str, ...], int]] = []
    if self.first_dense_layers:
      segments.append(((kinds[0],), self.first_dense_layers))
    kinds = kinds[self.first_dense_layers:]
    cyc = tuple(self.block_cycle)
    reps = len(kinds) // len(cyc)
    if reps > 0:
      segments.append((cyc, reps))
    rem = kinds[reps * len(cyc):]
    if rem:
      segments.append((tuple(rem), 1))
    return segments


def register(cfg: ArchConfig) -> ArchConfig:
  assert cfg.name not in _REGISTRY, cfg.name
  _REGISTRY[cfg.name] = cfg
  return cfg


def get_config(name: str) -> ArchConfig:
  if name not in _REGISTRY:
    # Import the module of the same name to trigger registration.
    import importlib
    mod = name.replace("-", "_").replace(".", "_")
    importlib.import_module(f"repro.configs.{mod}")
  return _REGISTRY[name]


def registered() -> list[str]:
  return sorted(_REGISTRY)


def parse_overrides(pairs):
  """``["key=value", ...]`` (the ``--set`` flags) -> ``ArchConfig`` overrides,
  with values cast to int, float or bool where they parse as one."""
  out = {}
  for pair in pairs or []:
    k, v = pair.split("=", 1)
    for cast in (int, float):
      try:
        out[k] = cast(v)
        break
      except ValueError:
        continue
    else:
      if v in ("True", "False"):
        out[k] = v == "True"
      else:
        out[k] = v
  return out


def all_assigned() -> list[str]:
  """The 10 assigned architectures (import side-effect registers them)."""
  names = [
      "gemma3-12b", "stablelm-3b", "llama3.2-1b", "tinyllama-1.1b",
      "deepseek-v2-lite-16b", "grok-1-314b", "llava-next-mistral-7b",
      "recurrentgemma-2b", "xlstm-350m", "musicgen-large",
  ]
  for n in names:
    get_config(n)
  return names
