"""Multi-pod dry-run: prove every (arch x shape x mesh) cell lowers,
partitions, and fits - without TPU hardware.

MUST set the fake-device flag before ANY other import (jax locks the
device count on first init):
"""
import os  # noqa: E402

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro import configs  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models.config import SHAPES  # noqa: E402
from repro.models.model import build_model, input_specs  # noqa: E402
from repro.optim import AdamWConfig, adamw_init_specs, adamw_update  # noqa: E402
from repro.sharding import (  # noqa: E402
    LogicalRules,
    eval_shape_tree,
    spec_shardings,
)

RESULT_DIR = os.path.join(os.path.dirname(__file__), "../../..", "experiments", "dryrun")

# HLO line shape: `%name = TYPE all-reduce(...)` or tuple TYPE for
# multi-operand collectives; async pairs appear as -start/-done (count the
# start only).
_COLL_RE = re.compile(
    r"=\s+(\(?[a-z0-9]+\[[^=]*?)\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\("
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

# algorithmic traffic factor per collective kind (ring algorithms)
_COLL_FACTOR = {
    "all-gather": 1.0,        # each device receives ~result bytes
    "all-reduce": 2.0,        # reduce-scatter + all-gather
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}


def _tensor_bytes(type_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dtype, dims = m.group(1), m.group(2)
        size = _DTYPE_BYTES.get(dtype, 4)
        for d in dims.split(","):
            if d:
                size *= int(d)
        total += size
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Sum per-device collective traffic from the partitioned HLO.

    Result-type bytes are used per op (for all-gather that is the gathered
    output a device receives; for all-reduce the resident tensor), weighted
    by the ring-algorithm traffic factor per kind.  -done halves of async
    pairs are skipped via the -start capture.
    """
    per_kind: dict[str, float] = {}
    counts: dict[str, int] = {}
    for m in _COLL_RE.finditer(hlo_text):
        type_str, kind = m.group(1), m.group(2)
        b = _tensor_bytes(type_str)
        per_kind[kind] = per_kind.get(kind, 0.0) + b * _COLL_FACTOR[kind]
        counts[kind] = counts.get(kind, 0) + 1
    return {
        "bytes_by_kind": per_kind,
        "ops_by_kind": counts,
        "total_bytes": sum(per_kind.values()),
    }


def scale_depth(cfg, p: int):
    """Same-width config with p periods (for scan-body cost extrapolation:
    XLA's cost_analysis counts a scan body once, so roofline FLOPs /
    collective bytes are measured at depths 1 and 2 and extrapolated
    linearly to the full depth; memory comes from the full-depth compile)."""
    kw = {"n_layers": p * len(cfg.pattern)}
    if cfg.enc_layers:
        kw["enc_layers"] = max(1, cfg.enc_layers * p // cfg.periods)
    return dataclasses.replace(cfg, **kw)


def _skip_reason(cfg, shape) -> str | None:
    if shape.name == "long_500k" and not cfg.long_context_ok:
        return (
            "full quadratic attention at 524k context; shape requires "
            "sub-quadratic sequence mixing (see DESIGN.md)"
        )
    return None


# microbatch (gradient-accumulation) factors for the train shape: bounds
# the live activation/wgrad working set; a production lever (identical
# math, k sequential fwd+bwd passes accumulating sharded gradients)
MICROBATCH = {
    "jamba-v0.1-52b": 8,
    "llama3-8b": 2,
    "minitron-4b": 2,
    "qwen2-moe-a2.7b": 2,
}


def _grad_accum_loss(model, batch, params, k: int):
    """Mean loss/grads over k microbatches; grads stay param-sharded."""
    def split(x):
        return x.reshape(k, x.shape[0] // k, *x.shape[1:])

    mb = jax.tree.map(split, batch)

    def mb_step(acc, mbatch):
        (loss, metrics), grads = jax.value_and_grad(
            model.loss, has_aux=True
        )(params, mbatch)
        acc = jax.tree.map(jnp.add, acc, grads)
        return acc, (loss, metrics)

    zeros = jax.tree.map(jnp.zeros_like, params)
    grads, (losses, metrics) = jax.lax.scan(mb_step, zeros, mb)
    grads = jax.tree.map(lambda g: g / k, grads)
    metrics = jax.tree.map(jnp.mean, metrics)
    return (jnp.mean(losses), metrics), grads


def _compile_step(cfg, shape, mesh, rules, *, opt: bool, microbatch: int = 1):
    """Lower + compile one (config, shape) on a mesh.  Returns compiled."""
    model = build_model(cfg, rules)
    si = input_specs(cfg, shape)
    batch_sds = si.batch
    batch_shard = si.shardings(rules)
    p_specs = model.param_specs()
    p_sds = eval_shape_tree(p_specs)
    p_shard = spec_shardings(p_specs, rules)

    with mesh:
        if shape.step == "train":
            if opt:
                o_specs = adamw_init_specs(p_specs)
                o_sds = eval_shape_tree(o_specs)
                o_shard = spec_shardings(o_specs, rules)
                opt_cfg = AdamWConfig()

                def train_step(params, opt_state, batch):
                    if microbatch > 1:
                        (loss, metrics), grads = _grad_accum_loss(
                            model, batch, params, microbatch
                        )
                    else:
                        (loss, metrics), grads = jax.value_and_grad(
                            model.loss, has_aux=True
                        )(params, batch)
                    params, opt_state, om = adamw_update(
                        opt_cfg, grads, opt_state, params
                    )
                    metrics.update(om)
                    return params, opt_state, metrics

                lowered = jax.jit(
                    train_step,
                    in_shardings=(p_shard, o_shard, batch_shard),
                    out_shardings=(p_shard, o_shard, None),
                    donate_argnums=(0, 1),
                ).lower(p_sds, o_sds, batch_sds)
            else:
                def loss_fn(params, batch):
                    return model.loss(params, batch)[0]

                lowered = jax.jit(
                    loss_fn, in_shardings=(p_shard, batch_shard)
                ).lower(p_sds, batch_sds)
        elif shape.step == "prefill":
            cache_specs = model.cache_specs(
                shape.global_batch, shape.seq_len, long=False
            )
            cache_shard = spec_shardings(cache_specs, rules)

            def prefill_fn(params, batch):
                return model.prefill(params, batch)

            lowered = jax.jit(
                prefill_fn,
                in_shardings=(p_shard, batch_shard),
                out_shardings=(None, cache_shard),
            ).lower(p_sds, batch_sds)
        else:  # decode
            def decode_fn(params, batch):
                return model.decode_step(params, batch)

            lowered = jax.jit(
                decode_fn,
                in_shardings=(p_shard, batch_shard),
                out_shardings=(None, batch_shard["cache"]),
                donate_argnums=(1,),
            ).lower(p_sds, batch_sds)
        return lowered.compile()


def _cost_record(compiled) -> dict:
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())
    return {
        "flops": cost.get("flops", 0.0),
        "hlo_bytes": float(
            cost.get("bytes accessed", 0.0) or cost.get("bytes_accessed", 0.0)
        ),
        "coll_bytes": coll["total_bytes"],
        "coll_detail": coll,
    }


# -------------------------------------------------------- isomap cells ----
# The paper's own technique at production scale: n = 2^19 points (an order
# of magnitude beyond the paper's n=125k ceiling), D = 784 (EMNIST dim),
# b = 4096 logical block.  Each stage lowers as its own cell.

ISOMAP_N = 2**19
ISOMAP_D = 784
ISOMAP_B = 4096
ISOMAP_STAGES = ("knn", "apsp", "center", "power")


def lower_isomap_cell(stage: str, *, multi_pod: bool):
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from repro.core import apsp as apsp_mod
    from repro.core import centering, knn as knn_mod, spectral

    n, d_feat, b = ISOMAP_N, ISOMAP_D, ISOMAP_B
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = 512 if multi_pod else 256
    data_axis = ("pod", "data") if multi_pod else "data"
    rec = {
        "arch": "isomap", "shape": f"isomap_{stage}",
        "mesh": "2x16x16" if multi_pod else "16x16", "step": stage,
        "n": n, "b": b,
    }
    t0 = time.time()
    with mesh:
        if stage == "knn":
            # ring kNN: rows over "data", features over "model"; on the
            # multi-pod mesh each pod walks half the ring (split ring) and
            # the candidate lists merge across pods
            x_sds = jax.ShapeDtypeStruct((n, ISOMAP_D), jnp.float32)
            x_shard = NamedSharding(mesh, P("data", "model"))

            def fn(x):
                return knn_mod.knn_ring(
                    x, k=10, mesh=mesh, row_axis="data", feat_axis="model",
                    split_axis="pod" if multi_pod else None,
                )

            lowered = jax.jit(fn, in_shardings=(x_shard,)).lower(x_sds)
        elif stage == "apsp":
            seg = apsp_mod.make_apsp_segment(
                mesh, n=n, b=b, data_axis=data_axis, model_axis="model"
            )
            g_sds = jax.ShapeDtypeStruct((n, n), jnp.float32)
            g_shard = NamedSharding(mesh, P(data_axis, "model"))
            lowered = jax.jit(
                seg, in_shardings=(g_shard, None, None),
                out_shardings=g_shard, donate_argnums=(0,),
            ).lower(
                g_sds,
                jax.ShapeDtypeStruct((), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.int32),
            )
        elif stage == "center":
            g_sds = jax.ShapeDtypeStruct((n, n), jnp.float32)
            g_shard = NamedSharding(mesh, P(data_axis, "model"))
            smfn = jax.shard_map(
                lambda t: centering.double_center_local(
                    jnp.square(t), data_axis=data_axis, model_axis="model",
                    n=n,
                ),
                mesh=mesh,
                in_specs=P(data_axis, "model"),
                out_specs=P(data_axis, "model"),
                check_vma=False,
            )
            lowered = jax.jit(
                smfn, in_shardings=(g_shard,), out_shardings=g_shard,
                donate_argnums=(0,),
            ).lower(g_sds)
        else:  # power
            eig = spectral.make_power_iteration_sharded(
                mesh, n=n, d=3, max_iter=100, tol=1e-9,
                data_axis=data_axis, model_axis="model",
            )
            g_sds = jax.ShapeDtypeStruct((n, n), jnp.float32)
            lowered = eig.lower(g_sds)

        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())
    rec.update(
        status="ok",
        lower_s=round(t_lower, 1),
        compile_s=round(t_compile, 1),
        flops_module=cost.get("flops", 0.0),
        hlo_bytes_module=float(
            cost.get("bytes accessed", 0.0) or cost.get("bytes_accessed", 0.0)
        ),
        coll_module=coll,
        memory={
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
        },
        chips=chips,
    )
    return rec


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool, opt: bool = True):
    """Lower + compile one cell.  The full-depth compile is the pass/fail
    proof + memory analysis; two reduced-depth compiles (1 and 2 periods)
    provide exact scan-body costs for the roofline extrapolation."""
    cfg = configs.get_config(arch)
    shape = SHAPES[shape_name]
    reason = _skip_reason(cfg, shape)
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "step": shape.step,
    }
    if reason:
        rec["status"] = "skipped"
        rec["reason"] = reason
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = LogicalRules(mesh)
    chips = 512 if multi_pod else 256

    mb = MICROBATCH.get(arch, 1) if shape.step == "train" else 1
    t0 = time.time()
    compiled = _compile_step(cfg, shape, mesh, rules, opt=opt, microbatch=mb)
    t_compile = time.time() - t0
    mem = compiled.memory_analysis()
    full_cost = _cost_record(compiled)

    # depth extrapolation (scan bodies are counted once by cost_analysis)
    t0 = time.time()
    c1 = _cost_record(
        _compile_step(scale_depth(cfg, 1), shape, mesh, rules, opt=opt)
    )
    c2 = _cost_record(
        _compile_step(scale_depth(cfg, 2), shape, mesh, rules, opt=opt)
    )
    t_extra = time.time() - t0
    periods = cfg.periods

    def extrap(key):
        body = c2[key] - c1[key]
        return c1[key] + body * (periods - 1)

    model = build_model(cfg)
    rec.update(
        status="ok",
        compile_s=round(t_compile, 1),
        extrap_compile_s=round(t_extra, 1),
        flops=extrap("flops"),
        hlo_bytes=extrap("hlo_bytes"),
        coll_bytes=extrap("coll_bytes"),
        flops_module=full_cost["flops"],
        hlo_bytes_module=full_cost["hlo_bytes"],
        coll_module=full_cost["coll_detail"],
        memory={
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
        },
        chips=chips,
        active_params=model.active_params(),
    )
    return rec


def run_isomap(meshes, out_dir=None):
    out_dir = out_dir or os.path.abspath(RESULT_DIR)
    os.makedirs(out_dir, exist_ok=True)
    results = []
    for stage in ISOMAP_STAGES:
        for mp in meshes:
            tag = f"isomap__{stage}__{'multipod' if mp else 'pod'}"
            path = os.path.join(out_dir, tag + ".json")
            if os.path.exists(path):
                with open(path) as f:
                    results.append(json.load(f))
                print(f"[dryrun] cached {tag}: {results[-1]['status']}")
                continue
            print(f"[dryrun] {tag} ...", flush=True)
            try:
                rec = lower_isomap_cell(stage, multi_pod=mp)
            except Exception as e:  # noqa: BLE001
                rec = {
                    "arch": "isomap", "shape": f"isomap_{stage}",
                    "mesh": "2x16x16" if mp else "16x16",
                    "status": "error",
                    "error": f"{type(e).__name__}: {e}",
                    "trace": traceback.format_exc()[-2000:],
                }
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            print(f"[dryrun] {tag}: {rec['status']} "
                  f"{rec.get('compile_s', rec.get('error', ''))}", flush=True)
            results.append(rec)
    return results


def run(arch_list, shape_list, meshes, out_dir=None, opt=True):
    out_dir = out_dir or os.path.abspath(RESULT_DIR)
    os.makedirs(out_dir, exist_ok=True)
    results = []
    for arch in arch_list:
        for shape_name in shape_list:
            for mp in meshes:
                tag = f"{arch}__{shape_name}__{'multipod' if mp else 'pod'}"
                path = os.path.join(out_dir, tag + ".json")
                if os.path.exists(path):
                    with open(path) as f:
                        rec = json.load(f)
                    print(f"[dryrun] cached {tag}: {rec['status']}")
                    results.append(rec)
                    continue
                print(f"[dryrun] {tag} ...", flush=True)
                try:
                    rec = lower_cell(arch, shape_name, multi_pod=mp, opt=opt)
                except Exception as e:  # noqa: BLE001
                    rec = {
                        "arch": arch, "shape": shape_name,
                        "mesh": "2x16x16" if mp else "16x16",
                        "status": "error",
                        "error": f"{type(e).__name__}: {e}",
                        "trace": traceback.format_exc()[-2000:],
                    }
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                print(
                    f"[dryrun] {tag}: {rec['status']}"
                    + (
                        f" compile={rec.get('compile_s')}s "
                        f"flops={rec.get('flops'):.3g}"
                        if rec["status"] == "ok"
                        else f" {rec.get('error', rec.get('reason', ''))}"
                    ),
                    flush=True,
                )
                results.append(rec)
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["pod", "multipod", "both"])
    ap.add_argument("--out-dir", default=None)
    ap.add_argument("--no-opt", dest="opt", action="store_false",
                    help="lower loss-only train step (no optimizer)")
    ap.add_argument("--isomap", action="store_true",
                    help="lower the isomap pipeline cells instead of archs")
    args = ap.parse_args()
    meshes = {"pod": [False], "multipod": [True], "both": [False, True]}[args.mesh]
    if args.isomap:
        results = run_isomap(meshes, args.out_dir)
    else:
        arch_list = list(configs.ARCHS) if args.arch == "all" else args.arch.split(",")
        shape_list = list(SHAPES) if args.shape == "all" else args.shape.split(",")
        results = run(arch_list, shape_list, meshes, args.out_dir, args.opt)
    ok = sum(r["status"] == "ok" for r in results)
    sk = sum(r["status"] == "skipped" for r in results)
    err = sum(r["status"] == "error" for r in results)
    print(f"[dryrun] done: {ok} ok, {sk} skipped, {err} errors")
    if err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
