import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry run: lower + compile every (arch x shape x mesh) cell.

For each cell this builds the production mesh (16x16 single-pod or 2x16x16
multi-pod), constructs fully-sharded train/prefill/decode steps from
ShapeDtypeStruct stand-ins (no allocation), compiles the SPMD program, and
records memory analysis + XLA cost analysis + the while-aware HLO cost
parse + roofline terms into experiments/dryrun/<cell>.json.

  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-12b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

Variants (hillclimbing knobs) apply config overrides and tag the output:
  --set seq_shard_activations=True --set q_chunk=1024 --tag spq1024
"""

import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis.hlo import analyze_text
from repro.analysis.roofline import (
    count_active_params, model_flops, roofline_terms)
from repro.configs.base import all_assigned, get_config, parse_overrides
from repro.launch import shapes as SH
from repro.launch import steps as ST
from repro.launch.mesh import data_axes_of, make_production_mesh
from repro.models import transformer as T
from repro.optim import adamw
from repro.sharding import specs as SP

DEFAULT_OUT = os.path.join(os.path.dirname(__file__),
                           "../../../experiments/dryrun")


def _named(mesh, spec_tree):
  return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                      is_leaf=lambda x: isinstance(x, P))


def lower_cell(cfg, cell, mesh):
  """Returns (lowered, aux_info)."""
  rules = SP.ShardingRules(
      mesh,
      data_axes=data_axes_of(mesh),
      model_axis="model",
      seq_shard_activations=cfg.seq_shard_activations,
      fsdp=cfg.fsdp,
  )
  key = jax.random.PRNGKey(0)
  params_shape = jax.eval_shape(lambda: T.init_params(cfg, key))
  pspecs = SP.param_specs_tree(rules, params_shape)
  pshard = _named(mesh, pspecs)
  info = {}

  with mesh, SP.use_rules(rules):
    if cell.kind == "train":
      opt_cfg = adamw.AdamWConfig(
          moment_dtype="bfloat16" if cfg.fsdp else "float32")
      opt_shape = jax.eval_shape(
          lambda p: ST.init_opt_state(cfg, opt_cfg, p), params_shape)
      ospecs = SP.opt_state_specs_tree(rules, opt_shape, pspecs)
      oshard = _named(mesh, ospecs)
      batch = SH.batch_specs(cfg, cell)
      bspecs = SP.batch_specs_tree(rules, batch)
      bshard = _named(mesh, bspecs)
      step = ST.make_train_step(cfg, opt_cfg)
      jitted = jax.jit(
          step,
          in_shardings=(pshard, oshard, bshard),
          out_shardings=(pshard, oshard, None),
          donate_argnums=(0, 1),
      )
      lowered = jitted.lower(params_shape, opt_shape, batch)
    elif cell.kind == "prefill":
      batch = SH.batch_specs(cfg, cell)
      bspecs = SP.batch_specs_tree(rules, batch)
      bshard = _named(mesh, bspecs)
      step = ST.make_prefill_step(cfg)
      jitted = jax.jit(step, in_shardings=(pshard, bshard))
      lowered = jitted.lower(params_shape, batch)
    else:  # decode
      caches = SH.cache_specs(cfg, cell)
      cspecs = SP.cache_specs_tree(rules, caches)
      cshard = _named(mesh, cspecs)
      tok = SH.decode_token_specs(cfg, cell)
      tok_spec = NamedSharding(
          mesh, rules.spec(tok.shape, (rules.data_axes,) + (None,) *
                           (len(tok.shape) - 1)))
      pos = jax.ShapeDtypeStruct((), jnp.int32)
      step = ST.make_decode_step(cfg)
      jitted = jax.jit(
          step,
          in_shardings=(pshard, cshard, tok_spec, NamedSharding(mesh, P())),
          out_shardings=(None, cshard),
          donate_argnums=(1,),
      )
      lowered = jitted.lower(params_shape, caches, tok, pos)

  total, active = count_active_params(cfg, params_shape)
  info["params_total"] = total
  info["params_active"] = active
  return lowered, info


def run_cell(arch, shape_name, multi_pod, overrides, outdir, force=False,
             tag="", keep_hlo=False):
  mesh_name = "multi" if multi_pod else "single"
  cell_id = f"{arch}__{shape_name}__{mesh_name}" + (f"__{tag}" if tag else "")
  os.makedirs(outdir, exist_ok=True)
  path = os.path.join(outdir, cell_id + ".json")
  if os.path.exists(path) and not force:
    print(f"[skip] {cell_id} (cached)")
    return json.load(open(path))

  cfg = get_config(arch)
  if overrides:
    cfg = dataclasses.replace(cfg, **overrides)
  cell = SH.SHAPES[shape_name]
  ok, why = SH.cell_applicable(cfg, cell)
  record = {
      "arch": arch, "shape": shape_name, "mesh": mesh_name, "tag": tag,
      "overrides": overrides or {},
  }
  if not ok:
    record.update({"status": "skipped", "reason": why})
    json.dump(record, open(path, "w"), indent=1)
    print(f"[skip] {cell_id}: {why}")
    return record

  mesh = make_production_mesh(multi_pod=multi_pod)
  n_dev = mesh.size
  t0 = time.time()
  try:
    lowered, info = lower_cell(cfg, cell, mesh)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    mem_rec = {
        "argument_bytes": mem.argument_size_in_bytes,
        "output_bytes": mem.output_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
        "alias_bytes": mem.alias_size_in_bytes,
        "peak_estimate_bytes": (mem.argument_size_in_bytes +
                                mem.output_size_in_bytes +
                                mem.temp_size_in_bytes -
                                mem.alias_size_in_bytes),
    }
    ca = compiled.cost_analysis() or {}
    hlo_text = compiled.as_text()
    parsed = analyze_text(hlo_text)
    mf = model_flops(cfg, jax.eval_shape(
        lambda: T.init_params(cfg, jax.random.PRNGKey(0))), cell)
    roof = roofline_terms(parsed, n_dev, mf)

    record.update({
        "status": "ok",
        "devices": n_dev,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "params_total": info["params_total"],
        "params_active": info["params_active"],
        "memory": mem_rec,
        "xla_cost_analysis": {k: float(v) for k, v in ca.items()
                              if isinstance(v, (int, float))},
        "hlo_parsed": parsed,
        "roofline": roof,
    })
    if keep_hlo:
      hlo_path = os.path.join(outdir, cell_id + ".hlo.txt")
      with open(hlo_path, "w") as f:
        f.write(hlo_text)
      record["hlo_path"] = hlo_path
    print(f"[ok]   {cell_id}: compile {t_compile:.0f}s, "
          f"dominant={roof['dominant']} ({roof['bound_s']*1e3:.2f} ms), "
          f"roofline_frac={roof['roofline_fraction']:.3f}, "
          f"mem/dev={mem_rec['peak_estimate_bytes']/2**30:.2f} GiB")
  except Exception as e:  # record failures — they are bugs to fix
    record.update({"status": "error", "error": repr(e),
                   "traceback": traceback.format_exc()})
    print(f"[FAIL] {cell_id}: {e}")
  json.dump(record, open(path, "w"), indent=1)
  return record


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument("--arch", default=None)
  ap.add_argument("--shape", default=None, choices=list(SH.SHAPES) + [None])
  ap.add_argument("--mesh", default="single",
                  choices=["single", "multi", "both"])
  ap.add_argument("--all", action="store_true")
  ap.add_argument("--force", action="store_true")
  ap.add_argument("--keep-hlo", action="store_true")
  ap.add_argument("--out", default=DEFAULT_OUT)
  ap.add_argument("--tag", default="")
  ap.add_argument("--set", action="append", dest="overrides",
                  help="config override key=value (repeatable)")
  args = ap.parse_args()

  archs = all_assigned() if (args.all or not args.arch) else [args.arch]
  shapes = list(SH.SHAPES) if (args.all or not args.shape) else [args.shape]
  meshes = {"single": [False], "multi": [True],
            "both": [False, True]}[args.mesh]
  overrides = parse_overrides(args.overrides)

  n_fail = 0
  for arch in archs:
    for shape in shapes:
      for multi in meshes:
        rec = run_cell(arch, shape, multi, overrides, args.out,
                       force=args.force, tag=args.tag,
                       keep_hlo=args.keep_hlo)
        n_fail += rec.get("status") == "error"
  raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
  main()
