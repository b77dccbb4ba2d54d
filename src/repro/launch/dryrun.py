"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

THE VERY FIRST LINES set XLA_FLAGS before any jax import — jax locks the
device count at first init.  Do NOT import this module from test/bench
processes that want 1 device; run it as ``python -m repro.launch.dryrun``.

Per cell this produces (and caches to experiments/dryrun/<cell>.json):
  * compiled.memory_analysis(): per-device argument/output/temp bytes
    (proves the cell fits 16 GB HBM),
  * compiled.cost_analysis(): per-device HLO FLOPs + bytes accessed,
  * collective bytes + op counts parsed from the partitioned HLO
    (all-gather / all-reduce / reduce-scatter / all-to-all /
    collective-permute) — cost_analysis does not report these,
  * lowering/compile wall time.

The roofline table (EXPERIMENTS.md §Roofline) is derived from these JSONs by
``repro.launch.roofline``.
"""
import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count="
                           + os.environ.get("DRYRUN_DEVICES", "512")).strip()

import argparse          # noqa: E402
import dataclasses       # noqa: E402
import json              # noqa: E402
import re                # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import sharding as shd                      # noqa: E402
from repro.configs import ARCHS, get_config, long_context_ok  # noqa: E402
from repro.launch.mesh import make_production_mesh     # noqa: E402
from repro.launch.shapes import SHAPES, ShapeCell      # noqa: E402
from repro.models import (abstract_cache, abstract_params, cache_specs,  # noqa: E402
                          decode_step, forward, param_specs)
from repro.models.config import ModelConfig            # noqa: E402
from repro.train import (TrainHyper, init_train_state, make_train_step,  # noqa: E402
                         train_state_specs)

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "experiments", "dryrun")

# Per-arch production layouts (validated in EXPERIMENTS.md §Perf):
# models too small for 16-way tensor parallelism run pure-DP across the
# whole mesh (mamba2 train collective term: 90 GiB -> 2.3 GiB/dev/step).
ARCH_RULES = {
    "mamba2-130m": shd.PURE_DP_RULES,
}

# Per-arch step hyper-parameters: microbatch counts chosen so the train
# cells fit 16 GiB HBM (yi-34b §Perf iteration log); grok additionally runs
# bf16 AdamW moments (params+opt 13.9 -> 9.5 GiB/dev).
import jax.numpy as _jnp                                   # noqa: E402
from repro.optim import OptConfig as _OptConfig            # noqa: E402

ARCH_HYPER = {
    "yi-34b": TrainHyper(microbatch=8),
    "grok-1-314b": TrainHyper(microbatch=8,
                              opt=_OptConfig(moment_dtype=_jnp.bfloat16)),
    "gemma3-12b": TrainHyper(microbatch=16),
    "recurrentgemma-2b": TrainHyper(microbatch=64),
    "whisper-small": TrainHyper(microbatch=64),
    "gemma-2b": TrainHyper(microbatch=64),
    "qwen2-vl-2b": TrainHyper(microbatch=64),
    "deepseek-moe-16b": TrainHyper(microbatch=64),
    "tinyllama-1.1b": TrainHyper(microbatch=64),
}

_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(
    r"=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_ARRAY_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dtype, dims in _ARRAY_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def parse_collectives(hlo_text: str) -> dict:
    """Sum per-device payload bytes of every collective in partitioned HLO.

    Convention (documented in EXPERIMENTS.md): bytes = output shape of the
    instruction; all-reduce counted twice (ring = reduce-scatter +
    all-gather).  `-start` variants (async) counted once; `-done` ignored.
    """
    out = {op: {"count": 0, "bytes": 0} for op in _COLLECTIVES}
    for m in _SHAPE_RE.finditer(hlo_text):
        shape_str, op = m.group(1), m.group(2)
        b = _shape_bytes(shape_str)
        out[op]["count"] += 1
        out[op]["bytes"] += b * (2 if op == "all-reduce" else 1)
    out["total_bytes"] = sum(v["bytes"] for k, v in out.items()
                             if isinstance(v, dict))
    return out


# ---------------------------------------------------------------------------
# Input specs per (arch, shape)
# ---------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, cell: ShapeCell) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of the cell, plus the
    logical sharding specs — no device allocation ever happens."""
    b, s = cell.global_batch, cell.seq_len
    extras_sds, extras_spec = {}, {}
    if cfg.encoder_layers:
        extras_sds["enc_frames"] = jax.ShapeDtypeStruct(
            (b, cfg.encoder_frames, cfg.d_model), cfg.dtype)
        extras_spec["enc_frames"] = ("batch", None, "embed_act")
    if cfg.vision_patches and cell.kind != "decode":
        extras_sds["patch_embeds"] = jax.ShapeDtypeStruct(
            (b, cfg.vision_patches, cfg.d_model), cfg.dtype)
        extras_spec["patch_embeds"] = ("batch", None, "embed_act")

    n_text = s - (cfg.vision_patches if cell.kind != "decode" else 0)
    if cell.kind == "train":
        sds = {"tokens": jax.ShapeDtypeStruct((b, n_text + 1), jnp.int32),
               **extras_sds}
        spec = {"tokens": ("batch", None), **extras_spec}
        return {"batch": sds, "batch_spec": spec}
    if cell.kind == "prefill":
        sds = {"tokens": jax.ShapeDtypeStruct((b, n_text), jnp.int32),
               **extras_sds}
        spec = {"tokens": ("batch", None), **extras_spec}
        return {"tokens": sds, "tokens_spec": spec}
    # decode: KV/state cache of seq_len + one new token
    return {
        "cache": abstract_cache(cfg, b, s),
        "cache_spec": cache_specs(cfg, b, s),
        "tokens": jax.ShapeDtypeStruct((b, 1), jnp.int32),
        "tokens_spec": ("batch", None),
    }


def _ns(mesh, rules, spec_tree, sds_tree):
    return shd.tree_named_shardings(spec_tree, sds_tree, mesh, rules)


def build_lowered(arch: str, shape_name: str, mesh, rules=shd.FSDP_RULES,
                  cfg: ModelConfig | None = None, hyper: TrainHyper | None = None,
                  compress: bool = False, dp_axes: tuple[str, ...] | None = None):
    """Lower the cell's step function with full sharding annotations.

    ``compress``: Seeker coreset gradient compression over the DP axes
    (train cells only; pairs with DP_TP_RULES — params replicated on data)."""
    cfg = cfg or get_config(arch)
    cell = SHAPES[shape_name]
    hyper = hyper or TrainHyper()
    specs = input_specs(cfg, cell)
    p_sds = abstract_params(cfg)
    p_spec = param_specs(cfg)

    with shd.use_sharding(mesh, rules):
        if cell.kind == "train":
            from repro.core.compression import CompressionConfig
            ccfg = CompressionConfig() if compress else None
            state_sds = jax.eval_shape(
                lambda: init_train_state(jax.random.PRNGKey(0), cfg, hyper,
                                         ccfg))
            state_spec = train_state_specs(cfg, ccfg)
            state_sh = _ns(mesh, rules, state_spec, state_sds)
            batch_sh = _ns(mesh, rules, specs["batch_spec"], specs["batch"])
            metrics_sh = jax.tree_util.tree_map(
                lambda _: jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec()),
                {"loss": 0, "grad_norm": 0, "lr": 0})
            if compress:
                from repro.train import make_compressed_train_step
                # manual (DP) axes = every axis the batch shards over,
                # unless the caller pins them (e.g. ("pod",) = compress the
                # slow inter-pod link only, dense ICI reduction within pod)
                batch_rule = rules.get("batch") or ()
                dp = dp_axes or tuple(
                    a for a in batch_rule if a in mesh.shape) or \
                    tuple(a for a in ("pod", "data") if a in mesh.shape)
                step = make_compressed_train_step(cfg, hyper, ccfg, mesh,
                                                  dp_axes=dp)
                jitted = jax.jit(step, donate_argnums=(0,))
            else:
                step = make_train_step(cfg, hyper)
                jitted = jax.jit(step, in_shardings=(state_sh, batch_sh),
                                 out_shardings=(state_sh, metrics_sh),
                                 donate_argnums=(0,))
            return jitted.lower(state_sds, specs["batch"])

        params_sh = _ns(mesh, rules, p_spec, p_sds)
        if cell.kind == "prefill":
            def prefill_step(params, batch):
                tokens = batch.pop("tokens")
                return forward(params, cfg, tokens, return_cache=True,
                               cache_len=cell.seq_len, **batch)

            tok_sh = _ns(mesh, rules, specs["tokens_spec"], specs["tokens"])
            jitted = jax.jit(prefill_step, in_shardings=(params_sh, tok_sh))
            return jitted.lower(p_sds, specs["tokens"])

        # decode
        def serve_step(params, cache, tokens):
            return decode_step(params, cfg, cache, tokens)

        cache_sh = _ns(mesh, rules, specs["cache_spec"], specs["cache"])
        tok_sh = jax.sharding.NamedSharding(
            mesh, shd.spec_for(specs["tokens_spec"], specs["tokens"].shape,
                               mesh, rules))
        jitted = jax.jit(serve_step,
                         in_shardings=(params_sh, cache_sh, tok_sh),
                         donate_argnums=(1,))
        return jitted.lower(p_sds, specs["cache"], specs["tokens"])


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             rules=shd.FSDP_RULES, tag: str = "", compress: bool = False,
             cfg: ModelConfig | None = None,
             hyper: TrainHyper | None = None,
             dp_axes: tuple[str, ...] | None = None) -> dict:
    cell = SHAPES[shape_name]
    mesh_name = "multi" if multi_pod else "single"
    result = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
              "tag": tag, "status": "ok"}
    cfg = cfg or get_config(arch)
    if rules is shd.FSDP_RULES:
        rules = ARCH_RULES.get(arch, rules)
    if hyper is None and cell.kind == "train":
        hyper = ARCH_HYPER.get(arch)
    if shape_name == "long_500k" and not long_context_ok(arch):
        result["status"] = "skipped"
        result["reason"] = ("pure full-attention arch: long_500k skipped per "
                            "assignment spec (see DESIGN.md §4)")
        return result
    try:
        t0 = time.time()
        mesh = make_production_mesh(multi_pod=multi_pod)
        lowered = build_lowered(arch, shape_name, mesh, rules=rules, cfg=cfg,
                                hyper=hyper, compress=compress,
                                dp_axes=dp_axes)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

        ca = compiled.cost_analysis() or {}
        result["cost_analysis"] = {
            "flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
            "transcendentals": float(ca.get("transcendentals", 0.0)),
        }
        try:
            ma = compiled.memory_analysis()
            result["memory_analysis"] = {
                "argument_bytes": int(ma.argument_size_in_bytes),
                "output_bytes": int(ma.output_size_in_bytes),
                "temp_bytes": int(ma.temp_size_in_bytes),
                "alias_bytes": int(ma.alias_size_in_bytes),
            }
        except Exception as e:  # pragma: no cover
            result["memory_analysis"] = {"error": str(e)}
        txt = compiled.as_text()
        result["collectives"] = parse_collectives(txt)   # raw (loop bodies once)
        from repro.launch.hlo_analysis import analyze_hlo
        result["hlo_analysis"] = analyze_hlo(txt).to_json()  # trip-count corrected
        result["hlo_chars"] = len(txt)
        result["timings"] = {"lower_s": round(t_lower, 2),
                             "compile_s": round(t_compile, 2)}
        result["n_devices"] = mesh.size
        result["params"] = cfg.param_count()
        result["active_params"] = cfg.active_param_count()
        result["cell"] = dataclasses.asdict(cell)
    except Exception as e:
        result["status"] = "error"
        result["error"] = f"{type(e).__name__}: {e}"
        result["traceback"] = traceback.format_exc()[-4000:]
    return result


def cell_path(arch: str, shape_name: str, mesh_name: str, tag: str = "") -> str:
    suffix = f"__{tag}" if tag else ""
    return os.path.join(RESULTS_DIR,
                        f"{arch}__{shape_name}__{mesh_name}{suffix}.json")


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, help="one arch id (default: all)")
    ap.add_argument("--shape", default=None, help="one shape (default: all)")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--rules", default="fsdp", choices=["fsdp", "dp_tp"])
    ap.add_argument("--tag", default="", help="suffix for result files")
    ap.add_argument("--force", action="store_true", help="recompute cached cells")
    ap.add_argument("--compress", action="store_true",
                    help="Seeker coreset gradient compression (train cells)")
    args = ap.parse_args()

    os.makedirs(RESULTS_DIR, exist_ok=True)
    archs = [args.arch] if args.arch else list(ARCHS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    rules = {"fsdp": shd.FSDP_RULES, "dp_tp": shd.DP_TP_RULES}[args.rules]

    n_ok = n_skip = n_err = 0
    for arch in archs:
        for shape in shapes:
            for multi in meshes:
                mesh_name = "multi" if multi else "single"
                path = cell_path(arch, shape, mesh_name, args.tag)
                if os.path.exists(path) and not args.force:
                    with open(path) as f:
                        prev = json.load(f)
                    if prev.get("status") in ("ok", "skipped"):
                        print(f"[cached] {arch} {shape} {mesh_name}: "
                              f"{prev['status']}")
                        continue
                print(f"[run]    {arch} {shape} {mesh_name} ...", flush=True)
                res = run_cell(arch, shape, multi, rules=rules, tag=args.tag,
                               compress=args.compress)
                with open(path, "w") as f:
                    json.dump(res, f, indent=1)
                if res["status"] == "ok":
                    n_ok += 1
                    ma = res.get("memory_analysis", {})
                    print(f"  ok: flops/dev={res['cost_analysis']['flops']:.3e}"
                          f" args/dev={ma.get('argument_bytes', 0)/2**30:.2f}GiB"
                          f" temp/dev={ma.get('temp_bytes', 0)/2**30:.2f}GiB"
                          f" coll/dev={res['collectives']['total_bytes']/2**30:.3f}GiB"
                          f" compile={res['timings']['compile_s']}s", flush=True)
                elif res["status"] == "skipped":
                    n_skip += 1
                    print(f"  skipped: {res['reason']}")
                else:
                    n_err += 1
                    print(f"  ERROR: {res['error']}")
    print(f"done: {n_ok} ok, {n_skip} skipped, {n_err} errors")


if __name__ == "__main__":
    main()
