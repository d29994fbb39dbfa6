"""Operator bytes and model FLOPs from shapes, on known shapes."""

import json
import os

from chipbench import counts

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _stablelm():
  with open(os.path.join(HERE, "configs", "stablelm-3b-lts.json")) as f:
    return json.load(f)


def test_soft_op_bytes_is_four_float32_arrays():
  # rank-loss: 1024 lists x 256 slate; the train step's row: 1 x 4096.
  assert counts.soft_op_bytes(1024 * 256) == 16 * 262144
  assert counts.soft_op_bytes(4096) == 65536


def test_matmul_params_at_published_widths():
  c = _stablelm()
  per_layer = 4 * 2560 * 2560 + 3 * 2560 * 6912
  assert counts.matmul_params(c) == 8 * per_layer + 2560 * 50304
  assert counts.matmul_params(dict(c, num_hidden_layers=32)) == \
      32 * per_layer + 2560 * 50304          # 2.67e9: the published 3B


def test_train_flops_adds_causal_attention():
  c = _stablelm()
  tokens = 4096
  want = 6 * counts.matmul_params(c) * tokens + 6 * 8 * 4096 * 2560 * tokens
  assert counts.train_flops(c, 1, 4096) == want
  assert abs(want / 20.817e12 - 1) < 1e-4    # 20.8 TFLOP per step
