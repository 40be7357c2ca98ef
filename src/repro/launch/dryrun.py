"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: builds the
production mesh from placeholder host devices, lowers the real step function
(train_step with AdamW+ZeRO-1, prefill, or decode_step) under pjit with the
full sharding rules, compiles it, and records memory_analysis / cost_analysis
/ per-collective byte totals for the roofline (EXPERIMENTS.md §Dry-run).

Usage:
    python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k --mesh single
    python -m repro.launch.dryrun --all --out results/dryrun.json
"""
import argparse
import json
import os
import re
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs.registry import ASSIGNED, SUBQUADRATIC, get
from ..configs.shapes import SHAPES, input_specs, sds
from ..distributed.sharding import (
    batch_axes, sharding_hints, tree_param_specs,
)
from ..models.model import ModelConfig, shapes_to_struct
from ..training.optimizer import AdamWConfig, adamw_update, init_opt_state, opt_state_specs
from .mesh import make_production_mesh


def force_placeholder_devices() -> None:
    """Give the CPU backend 512 placeholder devices for the production mesh.
    Must run before JAX initializes a backend, so launchers call it first;
    importing this module changes nothing."""
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# ------------------------------------------------------- collective parsing

_SHAPE_RE = re.compile(r"(pred|s4|s8|s16|s32|s64|u8|u16|u32|u64|f8\w*|bf16|f16|f32|f64)\[([0-9,]*)\]")
_COLL_RE = re.compile(
    r"=\s*(\([^)]*\)|\S+)\s+(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
)
_BYTES = {"pred": 1, "s4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
          "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8}


_COMP_RE = re.compile(r"^(?:ENTRY )?(%[\w.-]+) \([^)]*\) -> ", re.M)
_WHILE_RE = re.compile(
    r"while\([^)]*\),[^\n]*?body=(%[\w.-]+)[^\n]*?known_trip_count[^\d]*(\d+)")


def _shape_bytes(blob: str) -> int:
    total = 0
    for sm in _SHAPE_RE.finditer(blob):
        dtype, dims = sm.group(1), sm.group(2)
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        key = dtype if not dtype.startswith("f8") else "s8"
        total += n * _BYTES.get(key, 2)
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Sum output-shape bytes of every collective, multiplying ops inside
    while-loop bodies by their known trip counts (XLA cost/text represents a
    loop body once; the scanned layer stack would otherwise be undercounted
    by n_layers)."""
    # split into computations, attribute each collective to its computation
    comp_spans: list[tuple[str, int]] = [("<prelude>", 0)]
    for m in _COMP_RE.finditer(hlo_text):
        comp_spans.append((m.group(1), m.start()))
    comp_spans.append(("<end>", len(hlo_text)))

    def comp_of(pos: int) -> str:
        name = comp_spans[0][0]
        for cname, start in comp_spans[:-1]:
            if start <= pos:
                name = cname
            else:
                break
        return name

    # while nesting -> multiplier per computation
    mult: dict[str, int] = {}
    parents: list[tuple[str, str, int]] = []  # (parent comp, body comp, trip)
    for m in _WHILE_RE.finditer(hlo_text):
        parents.append((comp_of(m.start()), m.group(1), int(m.group(2))))
    changed = True
    passes = 0
    while changed and passes < 8:
        changed = False
        passes += 1
        for parent, body, trip in parents:
            want = trip * mult.get(parent, 1)
            if mult.get(body) != want:
                mult[body] = want
                changed = True

    out: dict[str, float] = {}
    count: dict[str, int] = {}
    for m in _COLL_RE.finditer(hlo_text):
        shapes_blob, kind = m.group(1), m.group(2)
        k = mult.get(comp_of(m.start()), 1)
        out[kind] = out.get(kind, 0) + _shape_bytes(shapes_blob) * k
        count[kind] = count.get(kind, 0) + k
    return {"bytes_by_kind": out, "count_by_kind": count,
            "total_bytes": sum(out.values()),
            "loop_multipliers": {k: v for k, v in mult.items() if v > 1}}


# ----------------------------------------------------------- cell execution


def activation_hints(cfg: ModelConfig, mesh, baxes) -> dict:
    """Baseline activation-sharding hints (the perf pass iterates on these)."""
    model_size = mesh.shape["model"]
    hints = {"residual": P(baxes, None, None)}
    if cfg.n_heads % model_size == 0 and cfg.kind in ("dense", "moe", "hybrid"):
        hints["attn_heads"] = P(baxes, "model", None, None)
    if cfg.d_ff and cfg.d_ff % model_size == 0:
        hints["mlp_hidden"] = P(baxes, None, "model")
    return hints


def cache_specs(cfg: ModelConfig, caches_shape, baxes, mesh, long_context: bool,
                kv_seq_shard: bool = False):
    """Sharding specs for decode caches.  KV caches shard batch normally; the
    long_500k (batch=1) shape shards the sequence axis across the whole mesh.
    ``kv_seq_shard`` (perf variant): additionally shard the KV sequence axis
    over 'model' — flash-decode style — so the model axis reads its own cache
    slice instead of all-gathering the cache when kv_heads < model shards."""
    model_size = mesh.shape["model"]
    all_axes = tuple(mesh.axis_names)

    def spec_for(path: str, leaf) -> P:
        shape = leaf.shape
        batch_ok = shape[1] % _axes_size(mesh, baxes) == 0
        b_ax = baxes if batch_ok else None
        if "state" in path:  # (L, B, H, P, N)
            if cfg.ssm_heads % model_size == 0:
                return P(None, b_ax, "model", None, None)
            return P(None, b_ax, None, None, None)
        if "conv" in path:  # (L, B, K-1, di)
            if cfg.d_inner % model_size == 0:
                return P(None, b_ax, None, "model")
            return P(None, b_ax, None, None)
        # KV caches: (L, B, Hkv, S, Dh)
        if long_context:
            return P(None, None, None, all_axes, None)
        if kv_seq_shard:
            return P(None, b_ax, None, "model", None)
        return P(None, b_ax, None, None, None)

    def walk(tree, path=""):
        if isinstance(tree, dict):
            return {k: walk(v, path + "/" + k) for k, v in tree.items()}
        if isinstance(tree, tuple):
            return tuple(walk(v, path) for v in tree)
        return spec_for(path, tree)

    return walk(caches_shape)


def _axes_size(mesh, axes) -> int:
    if isinstance(axes, str):
        return mesh.shape[axes]
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def run_cell(arch: str, shape_name: str, mesh_kind: str, with_opt: bool = True,
             hint_overrides: dict | None = None, variant: str = "baseline") -> dict:
    cfg = get(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    baxes = batch_axes(mesh.axis_names)
    baxes = baxes if len(baxes) > 1 else baxes[0]
    model_size = mesh.shape["model"]
    mod = cfg.build()

    pshapes = cfg.param_shapes()
    pstruct = shapes_to_struct(pshapes, cfg.dtype)
    pspecs = tree_param_specs(pshapes, model_size,
                              stacked_prefixes=("layers", "dense_layers", "mamba"))
    if variant.startswith("zero3_params"):
        # ZeRO-3-lite: params *stored* data+model sharded; XLA gathers the
        # stacked weights once per step in bf16, and the updated params are
        # written back sharded (no output gather at all)
        from ..training.optimizer import opt_state_specs as _oss

        _dax = batch_axes(mesh.axis_names)
        pspecs = _oss(pspecs, shapes_to_struct(pshapes, cfg.dtype),
                      _dax, _axes_size(mesh, _dax))["m"]
    pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                          is_leaf=lambda x: isinstance(x, P))

    ins = input_specs(cfg, shape)
    hints = activation_hints(cfg, mesh, baxes)
    if "sp" in variant and shape.kind != "decode":
        # Megatron-style sequence parallelism: residual stream sharded over
        # 'model' on the sequence axis between blocks
        hints["residual"] = P(baxes, "model", None)
    if hint_overrides:
        hints.update(hint_overrides)

    t0 = time.time()
    with mesh, sharding_hints(hints):
        if shape.kind == "train":
            opt_cfg = AdamWConfig()
            ostruct = jax.eval_shape(init_opt_state, pstruct)
            data_size = _axes_size(mesh, baxes if isinstance(baxes, tuple) else (baxes,))
            if variant == "no_zero1":
                ospecs = {"m": pspecs, "v": pspecs, "step": P()}
            elif variant.startswith("zero3_params"):
                # params already carry the data axis; moments share their specs
                ospecs = {"m": pspecs, "v": pspecs, "step": P()}
            else:
                ospecs = opt_state_specs(pspecs, pstruct,
                                         baxes if isinstance(baxes, tuple) else (baxes,),
                                         data_size)
            oshard = jax.tree.map(lambda s: NamedSharding(mesh, s), ospecs,
                                  is_leaf=lambda x: isinstance(x, P))
            bspec = {k: NamedSharding(mesh, P(baxes, *([None] * (len(v.shape) - 1))))
                     for k, v in ins.items()}

            mspecs = oshard["m"] if variant == "zero1_bf16_gather" else None

            def train_step(params, opt_state, batch):
                loss, grads = jax.value_and_grad(
                    lambda p: mod.loss_fn(cfg, p, batch))(params)
                new_p, new_o, gnorm = adamw_update(opt_cfg, params, grads, opt_state,
                                                   moment_specs=mspecs)
                return loss, gnorm, new_p, new_o

            jitted = jax.jit(
                train_step,
                in_shardings=(pshard, oshard, bspec),
                out_shardings=(NamedSharding(mesh, P()), NamedSharding(mesh, P()),
                               pshard, oshard),
                donate_argnums=(0, 1),
            )
            lowered = jitted.lower(pstruct, ostruct, ins)
        elif shape.kind == "prefill":
            bspec = {k: NamedSharding(mesh, P(baxes, *([None] * (len(v.shape) - 1))))
                     for k, v in ins.items()}

            def prefill_step(params, batch):
                return mod.prefill(cfg, params, cache_len=shape.seq_len, **batch)

            jitted = jax.jit(prefill_step, in_shardings=(pshard, bspec))
            lowered = jitted.lower(pstruct, ins)
        else:  # decode
            long_ctx = shape_name == "long_500k"
            cspecs = cache_specs(cfg, ins["caches"], baxes, mesh, long_ctx,
                                 kv_seq_shard=(variant == "kv_seq_shard"))
            cshard = jax.tree.map(lambda s: NamedSharding(mesh, s), cspecs,
                                  is_leaf=lambda x: isinstance(x, P))
            tok_spec = NamedSharding(mesh, P(baxes) if shape.global_batch >= 32 else P(None))

            def decode(params, token, caches, pos):
                return mod.decode_step(cfg, params, token, caches, pos)

            logits_spec = (NamedSharding(mesh, P(None, "model"))
                           if variant == "kv_seq_shard" and cfg.vocab % model_size == 0
                           else NamedSharding(mesh, P(None, None)))
            jitted = jax.jit(
                decode,
                in_shardings=(pshard, tok_spec, cshard, tok_spec),
                out_shardings=(logits_spec, cshard, tok_spec),
                donate_argnums=(2,),
            )
            lowered = jitted.lower(pstruct, ins["token"], ins["caches"], ins["pos"])
        lower_s = time.time() - t0
        t1 = time.time()
        compiled = lowered.compile()
        compile_s = time.time() - t1

        # cost probe: unrolled lowering (no compile) — XLA's HloCostAnalysis
        # counts loop bodies once, so the scanned module undercounts FLOPs by
        # ~n_layers; the unrolled module gives complete *global* FLOPs/bytes.
        t2 = time.time()
        from ..models.model import unrolled_scans

        try:
            with unrolled_scans():
                # fresh jit wrapper: the scan-unroll contextvar is not part of
                # jax's trace cache key, so the probe must force a re-trace
                if shape.kind == "train":
                    probe = jax.jit(lambda p, o, b: train_step(p, o, b))
                    unrolled = probe.lower(pstruct, ostruct, ins)
                elif shape.kind == "prefill":
                    probe = jax.jit(lambda p, b: prefill_step(p, b))
                    unrolled = probe.lower(pstruct, ins)
                else:
                    probe = jax.jit(lambda p, t, c, g: decode(p, t, c, g))
                    unrolled = probe.lower(pstruct, ins["token"], ins["caches"], ins["pos"])
            ucost = unrolled.cost_analysis() or {}
        except Exception as e:  # cost probe is best-effort
            ucost = {"error": f"{type(e).__name__}: {e}"}
        probe_s = time.time() - t2

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    coll = collective_bytes(compiled.as_text())
    return {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind, "variant": variant,
        "status": "ok", "lower_s": round(lower_s, 2), "compile_s": round(compile_s, 2),
        "probe_s": round(probe_s, 2),
        "flops_global": ucost.get("flops", 0.0),
        "bytes_global": ucost.get("bytes accessed", 0.0),
        "cost_probe_error": ucost.get("error"),
        "flops_per_device": cost.get("flops", 0.0),
        "bytes_accessed_per_device": cost.get("bytes accessed", 0.0),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "generated_code_bytes": mem.generated_code_size_in_bytes,
        },
        "collectives": coll,
        "params_total": cfg.param_count(),
        "params_active": cfg.active_param_count(),
    }


def cells(include_long: bool = True):
    for arch in ASSIGNED:
        for shape_name in SHAPES:
            if shape_name == "long_500k" and arch not in SUBQUADRATIC:
                continue  # full attention @524k context: skipped per DESIGN.md
            if shape_name == "long_500k" and not include_long:
                continue
            for mesh_kind in ("single", "multi"):
                yield arch, shape_name, mesh_kind


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun.json")
    ap.add_argument("--variant", default="baseline",
                    help="baseline | kv_seq_shard | no_zero1 | zero3_params | *_sp")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()
    force_placeholder_devices()

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    results = {}
    if os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)

    todo = (list(cells()) if args.all
            else [(args.arch, args.shape, args.mesh)])
    for arch, shape_name, mesh_kind in todo:
        key = f"{arch}|{shape_name}|{mesh_kind}"
        if args.variant != "baseline":
            key += f"|{args.variant}"
        if key in results and results[key].get("status") == "ok" and not args.force:
            print(f"SKIP {key}")
            continue
        print(f"RUN  {key} ...", flush=True)
        try:
            res = run_cell(arch, shape_name, mesh_kind, variant=args.variant)
        except Exception as e:  # record failures; they are bugs to fix
            res = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                   "status": "error", "error": f"{type(e).__name__}: {e}",
                   "trace": traceback.format_exc()[-2000:]}
        results[key] = res
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        status = res["status"]
        extra = (f"compile={res.get('compile_s')}s flops/dev={res.get('flops_per_device'):.3e}"
                 if status == "ok" else res.get("error", "")[:200])
        print(f"DONE {key}: {status} {extra}", flush=True)

    ok = sum(1 for r in results.values() if r.get("status") == "ok")
    print(f"\n{ok}/{len(results)} cells ok")


if __name__ == "__main__":
    main()
