import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (assignment deliverable e).

For every (architecture × input shape × mesh) cell: build ShapeDtypeStruct
inputs, jit the right step (train_step / prefill / serve_step) with explicit
in_shardings, ``.lower().compile()``, and record memory_analysis(),
cost_analysis() and the parsed collective schedule into a JSON file that
EXPERIMENTS.md §Dry-run/§Roofline and benchmarks/roofline.py consume.

NOTE: the XLA_FLAGS line above MUST stay the first statement — jax locks
the device count on first initialisation.
"""
import argparse
import json
import time
import traceback
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import all_archs, get_config
from repro.launch import hlo_analysis as H
from repro.launch.mesh import (
    axis_sizes,
    batch_axes,
    make_production_mesh,
)
from repro.models import build
from repro.models.config import SHAPES_BY_NAME, ShapeSpec
from repro.models.layers import Axes
from repro.models.zoo import Model
from repro.optim import AdamWConfig
from repro.serve.engine import make_decode_step
from repro.sharding import cache_pspecs, named_shardings, param_pspecs
from repro.train.step import TrainConfig, init_train_state, make_train_step

DEFAULT_OUT = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "experiments", "dryrun")


def skip_reason(model: Model, shape: ShapeSpec) -> Optional[str]:
    cfg = model.cfg
    if shape.name == "long_500k" and not cfg.supports_long_context:
        return ("pure full-attention arch: long_500k needs sub-quadratic "
                "sequence mixing (DESIGN.md §5)")
    return None


def make_axes(mesh, cp: bool = False) -> Axes:
    return Axes(batch=batch_axes(mesh), model="model", fsdp="data",
                seq="data" if cp else None,
                sizes=tuple(axis_sizes(mesh).items()))


def batch_pspecs(structs: Dict[str, jax.ShapeDtypeStruct], baxes,
                 sizes: Dict[str, int]):
    dp = 1
    for a in baxes:
        dp *= sizes.get(a, 1)

    def spec(s):
        lead = baxes if s.shape[0] % max(dp, 1) == 0 and s.shape[0] >= dp else None
        return P(lead, *([None] * (len(s.shape) - 1)))

    return {k: spec(v) for k, v in structs.items()}


def _opt_state_specs(pspecs):
    return {
        "step": P(),
        "m": pspecs,
        "v": pspecs,
        "master": pspecs,
    }


def input_specs(arch: str, shape_name: str) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins for every model input of a cell
    (assignment step 2) — no device allocation."""
    model = build(get_config(arch))
    return model.batch_shapes(SHAPES_BY_NAME[shape_name])


#: §Perf overrides: remat policy + microbatching per arch (train cells).
#: block_save keeps post-collective outputs (skips remat re-all-gathers);
#: microbatch counts bound activation residuals under 16 GB HBM/chip.
TRAIN_TUNING = {
    "dbrx-132b": {"microbatches": 16, "remat": "block"},
    "qwen2.5-3b": {"microbatches": 2},      # 15.2 GB temp at mb=2
    "mamba2-370m": {"microbatches": 2},     # 19.7 GB at mb=1: must split
    "olmoe-1b-7b": {"microbatches": 4, "remat": "block_save"},
    "gemma3-1b": {"remat": "block_save"},
    # llama3.2-3b / recurrentgemma-2b fit at mb=1 (4.0 / 6.1 GB x2):
    # microbatching them only doubles FSDP weight gathers.
}


def lower_cell(arch: str, shape_name: str, mesh) -> Tuple:
    """Build (jitted fn, arg structs, in_shardings) for one cell."""
    import dataclasses

    cfg = get_config(arch)
    tuning = TRAIN_TUNING.get(arch, {})
    if SHAPES_BY_NAME[shape_name].is_train and "remat" in tuning:
        cfg = dataclasses.replace(cfg, remat=tuning["remat"])
    model = build(cfg)
    shape = SHAPES_BY_NAME[shape_name]
    sizes = axis_sizes(mesh)
    baxes = batch_axes(mesh)
    cp = shape.name == "long_500k"      # context-parallel cache (batch=1)
    axes = make_axes(mesh, cp=cp
                     and cfg.family not in ("ssm",))
    params_struct = model.abstract_params()
    pspecs = param_pspecs(params_struct, sizes)

    if shape.is_train:
        microbatches = tuning.get("microbatches", 1)
        tcfg = TrainConfig(
            optimizer=AdamWConfig(mixed_precision=True),
            xent_chunk=512,   # pod-axis DP all-reduce comes from SPMD
            microbatches=microbatches,
        )
        state_struct = jax.eval_shape(
            lambda r: init_train_state(model, tcfg, r), jax.random.PRNGKey(0))
        state_specs = {
            "params": pspecs,
            "opt": _opt_state_specs(pspecs),
            "error": jax.tree_util.tree_map(lambda _: P(),
                                            state_struct["error"]),
        }
        batch_structs = model.batch_shapes(shape)
        bspecs = batch_pspecs(batch_structs, baxes, sizes)
        fn = make_train_step(model, axes, tcfg, grad_pspecs=pspecs)
        in_sh = (named_shardings(state_specs, mesh),
                 named_shardings(bspecs, mesh))
        return fn, (state_struct, batch_structs), in_sh, (0,)

    if shape.kind == "prefill":
        from repro.serve.engine import make_prefill

        batch_structs = model.batch_shapes(shape)
        bspecs = batch_pspecs(batch_structs, baxes, sizes)
        fn = make_prefill(model, axes)
        in_sh = (named_shardings(pspecs, mesh), named_shardings(bspecs, mesh))
        return fn, (params_struct, batch_structs), in_sh, ()

    # decode
    b = shape.global_batch
    s_text = model.text_len(shape.seq_len)
    enc_len = shape.seq_len - s_text if cfg.family == "encdec" else 0
    cache_struct = jax.eval_shape(
        lambda: model.init_cache(b, s_text + (cfg.n_frontend_tokens or 0),
                                 enc_len=enc_len))
    cspecs = cache_pspecs(cache_struct, baxes, sizes,
                          seq_shard=cp and cfg.family not in ("ssm",))
    tok_struct = jax.ShapeDtypeStruct((b, 1), jnp.int32)
    pos_struct = jax.ShapeDtypeStruct((b,), jnp.int32)
    dp = 1
    for a in baxes:
        dp *= sizes.get(a, 1)
    tok_spec = P(baxes, None) if b % dp == 0 and b >= dp else P(None, None)
    pos_spec = P(baxes) if b % dp == 0 and b >= dp else P(None)
    fn = make_decode_step(model, axes)
    in_sh = (named_shardings(pspecs, mesh),
             named_shardings(cspecs, mesh),
             NamedSharding(mesh, tok_spec), NamedSharding(mesh, pos_spec))
    return fn, (params_struct, cache_struct, tok_struct, pos_struct), in_sh, (1,)


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: str, verbose: bool = True,
             save_hlo: bool = False) -> Dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    cfg = get_config(arch)
    model = build(cfg)
    shape = SHAPES_BY_NAME[shape_name]
    mesh_tag = "multipod" if multi_pod else "singlepod"
    rec: Dict = {"arch": arch, "shape": shape_name, "mesh": mesh_tag,
                 "devices": int(n_dev)}

    reason = skip_reason(model, shape)
    if reason:
        rec["skipped"] = reason
        _write(out_dir, mesh_tag, arch, shape_name, rec)
        if verbose:
            print(f"[{mesh_tag}] {arch} × {shape_name}: SKIP ({reason})")
        return rec

    t0 = time.time()
    try:
        fn, structs, in_sh, donate = lower_cell(arch, shape_name, mesh)
        # set_mesh publishes the abstract mesh that shard_map-based
        # context parallelism resolves at trace time.
        with jax.set_mesh(mesh):
            jitted = jax.jit(fn, in_shardings=in_sh, donate_argnums=donate)
            lowered = jitted.lower(*structs)
            t_lower = time.time() - t0
            compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        ca = compiled.cost_analysis() or {}
        if isinstance(ca, (list, tuple)):   # older JAX: list of one dict
            ca = ca[0] if ca else {}
        ma = compiled.memory_analysis()
        hlo = compiled.as_text()
        if save_hlo:
            import gzip

            d = os.path.join(out_dir, mesh_tag)
            os.makedirs(d, exist_ok=True)
            with gzip.open(os.path.join(
                    d, f"{arch.replace('.', '_')}__{shape_name}.hlo.gz"),
                    "wt") as fh:
                fh.write(hlo)
        # Loop-corrected terms: XLA cost_analysis counts while (scan)
        # bodies once; we weight every instruction by its computation's
        # trip-count multiplier (hlo_analysis.loop_multipliers).
        mults = H.loop_multipliers(hlo)
        coll = H.collective_stats(hlo, n_dev)
        flops_dev = H.dot_flops(hlo, mults)
        bytes_dev = H.memory_bytes(hlo, mults)
        rl = H.roofline_terms(flops_dev, bytes_dev, coll.ici_bytes_per_chip)

        tokens = shape.global_batch * (shape.seq_len if shape.is_train or
                                       shape.kind == "prefill" else 1)
        mf = H.model_flops(cfg.param_count(), tokens,
                           "train" if shape.is_train else "serve",
                           active_param_count=_active_params(cfg))
        rec.update({
            "ok": True,
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "flops_per_device": flops_dev,
            "bytes_per_device": bytes_dev,
            "cost_analysis_raw": {          # uncorrected (while-body-once)
                "flops": float(ca.get("flops", 0.0)),
                "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
            },
            "collective": {
                "ops": coll.ops,
                "result_bytes": coll.bytes_by_kind,
                "ici_bytes_per_chip": coll.ici_bytes_per_chip,
            },
            "roofline": {
                "compute_s": rl.compute_s,
                "memory_s": rl.memory_s,
                "collective_s": rl.collective_s,
                "dominant": rl.dominant,
            },
            "memory": {
                "argument_bytes": int(getattr(ma, "argument_size_in_bytes", 0)),
                "output_bytes": int(getattr(ma, "output_size_in_bytes", 0)),
                "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
                "generated_code_bytes": int(
                    getattr(ma, "generated_code_size_in_bytes", 0)),
            },
            "model_flops_total": mf,
            "model_flops_ratio": (mf / (flops_dev * n_dev)
                                  if flops_dev else 0.0),
        })
        if verbose:
            print(f"[{mesh_tag}] {arch} × {shape_name}: OK "
                  f"compile={t_compile:.1f}s dominant={rl.dominant} "
                  f"comp={rl.compute_s:.2e}s mem={rl.memory_s:.2e}s "
                  f"coll={rl.collective_s:.2e}s")
    except Exception as e:  # noqa: BLE001 — a failing cell is a bug report
        rec.update({"ok": False, "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-2000:]})
        if verbose:
            print(f"[{mesh_tag}] {arch} × {shape_name}: FAIL {type(e).__name__}: {e}")
    _write(out_dir, mesh_tag, arch, shape_name, rec)
    return rec


def _active_params(cfg) -> Optional[int]:
    if cfg.family != "moe":
        return None
    dense = cfg.param_count()
    expert_all = cfg.n_layers * cfg.n_experts * 3 * cfg.d_model * cfg.d_ff
    expert_active = cfg.n_layers * cfg.experts_per_token * 3 * cfg.d_model * cfg.d_ff
    return dense - expert_all + expert_active


def _write(out_dir, mesh_tag, arch, shape_name, rec):
    d = os.path.join(out_dir, mesh_tag)
    os.makedirs(d, exist_ok=True)
    fname = f"{arch.replace('.', '_')}__{shape_name}.json"
    with open(os.path.join(d, fname), "w") as f:
        json.dump(rec, f, indent=1)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", choices=["singlepod", "multipod", "both"],
                    default="both")
    ap.add_argument("--out", default=os.path.abspath(DEFAULT_OUT))
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--save-hlo", action="store_true",
                    help="also write gzipped optimized HLO per cell")
    args = ap.parse_args()

    archs = all_archs() if args.arch == "all" else [args.arch]
    shapes = (list(SHAPES_BY_NAME) if args.shape == "all" else [args.shape])
    meshes = (["singlepod", "multipod"] if args.mesh == "both"
              else [args.mesh])
    failures = 0
    for mesh_tag in meshes:
        for arch in archs:
            for shape_name in shapes:
                path = os.path.join(
                    args.out, mesh_tag,
                    f"{arch.replace('.', '_')}__{shape_name}.json")
                if args.skip_existing and os.path.exists(path):
                    with open(path) as f:
                        old = json.load(f)
                    if old.get("ok") or old.get("skipped"):
                        continue
                rec = run_cell(arch, shape_name, mesh_tag == "multipod",
                               args.out, save_hlo=args.save_hlo)
                if not (rec.get("ok") or rec.get("skipped")):
                    failures += 1
    print(f"dry-run complete; failures={failures}")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
