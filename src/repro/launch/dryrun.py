import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and extract memory/cost/collective analyses.

MUST be run as its own process (``python -m repro.launch.dryrun``): the
XLA_FLAGS line above executes before any jax import, forcing 512 placeholder
CPU devices so ``jax.make_mesh`` can build the (2,16,16) production mesh.

Usage:
  python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out runs/dryrun]
"""

import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import (  # noqa: E402
    ARCH_NAMES,
    SHAPES,
    cell_supported,
    get_config,
    input_specs,
)
from repro.dist import sharding as shd  # noqa: E402
from repro.dist.plan import use_plan  # noqa: E402
from repro.launch.mesh import make_plan, make_production_mesh  # noqa: E402
from repro.models import transformer as tf  # noqa: E402
from repro.optim import sgd  # noqa: E402
from repro.train.engine import StepEngine  # noqa: E402
from repro.train.state import init_state  # noqa: E402
from repro.utils import hlo as hlo_lib  # noqa: E402
from repro.utils import pytree as ptu  # noqa: E402
from repro.utils.logging import get_logger  # noqa: E402

log = get_logger("dryrun")

GIB = 1 << 30

# Per-cell tuning knobs discovered during the perf iteration (EXPERIMENTS.md
# §Perf). Keys: (arch, shape) -> dict of overrides.
CELL_TUNING: dict[tuple[str, str], dict] = {
    # §Perf B3 (EXPERIMENTS.md): FSDP weight re-gathers scale with the
    # accumulation length; 4 microbatches cut the collective term
    # 372s -> 245s (-34%) for +6 GiB of activation footprint.
    ("llama3-405b", "train_4k"): {"num_micro": 4},
    # §Perf D1: larger SSM chunks -> fewer chunk-scan boundaries (stacked ys
    # writes): memory term 73s -> 52s (-29%) for +0.9 GiB.
    ("falcon-mamba-7b", "train_4k"): {"config": {"ssm_chunk": 1024}},
}


def dryrun_config(arch: str):
    """The production-run variant of an arch config (bf16, scan, remat)."""
    cfg = get_config(arch)
    overrides = dict(
        scan_layers=True,
        remat=True,
        param_dtype="bfloat16",
        compute_dtype="bfloat16",
        # q-block axis must be divisible by the 16-way model axis for the
        # Ulysses-style attention sharding (see act_specs_for)
        flash_q_block=256,
        flash_kv_block=1024,
    )
    return cfg.replace(**overrides)


def _micro_plan(cfg, shape, plan) -> tuple[int, int]:
    """(num_micro, micro_global_batch) for a train cell: pick ~1 sequence per
    dp shard for giant models, more for small ones."""
    dp = plan.dp_size
    b = shape.global_batch
    # 1 sequence/shard for giant dense models, big-E MoE, and hybrids (whose
    # mamba chunk scans carry (B, L, d_inner, d_state) working sets)
    heavy = (
        cfg.d_model >= 6144
        or cfg.num_experts >= 64
        or ("mamba" in cfg.pattern and cfg.num_experts > 0)
    )
    seqs_per_shard = 1 if heavy else 4
    micro = min(b, dp * seqs_per_shard)
    while b % micro != 0:
        micro //= 2
    micro = max(micro, 1)
    return b // micro, micro


def build_train(cfg, shape, plan, tuning):
    opt_dtype = jnp.bfloat16 if cfg.d_model >= 6144 or cfg.num_experts >= 64 else jnp.float32
    div_dtype = opt_dtype
    optimizer = sgd(momentum=0.9, state_dtype=opt_dtype)
    num_micro, micro = _micro_plan(cfg, shape, plan)
    num_micro = tuning.get("num_micro", num_micro)
    moe_groups = plan.dp_size if cfg.num_experts else 1

    params_specs = tf.param_specs(cfg)
    state_specs = jax.eval_shape(lambda p: init_state(p, optimizer, div_dtype), params_specs)
    state_ps = shd.infer_pspecs(state_specs, plan)
    state_sh = shd.shardings_of(state_ps, plan)

    batch_specs = input_specs(cfg, shape)["batch"]
    batch_ps = shd.batch_pspecs(batch_specs, plan)
    batch_sh = shd.shardings_of(batch_ps, plan)

    lr_spec = jax.ShapeDtypeStruct((), jnp.float32)

    # Same engine as Trainer/launch.train: one donated, bucketed step program
    # per num_micro; the dry-run AOT-lowers the jitted fn for one bucket.
    engine = StepEngine.for_lm(
        cfg, optimizer, dp_size=plan.dp_size, moe_groups=moe_groups,
        diversity_on=True, grad_accum_dtype=opt_dtype,
        in_shardings=(state_sh, batch_sh, NamedSharding(plan.mesh, P())),
        out_shardings=(state_sh, None),
    )
    jitted = engine.jitted(num_micro)
    args = (state_specs, batch_specs, lr_spec)
    info = {"num_micro": num_micro, "micro_global": micro,
            "opt_dtype": str(opt_dtype.__name__ if hasattr(opt_dtype, '__name__') else opt_dtype)}
    return jitted, args, info


def build_prefill(cfg, shape, plan, tuning):
    specs = input_specs(cfg, shape)["batch"]
    batch_ps = shd.batch_pspecs(specs, plan)
    batch_sh = shd.shardings_of(batch_ps, plan)
    params_specs = tf.param_specs(cfg)
    params_sh = shd.shardings_of(shd.infer_pspecs(params_specs, plan), plan)

    # MoE prefill must route tokens in groups: a single group over 1M tokens
    # builds an (E, T*k*cf/E, d) dispatch buffer plus a (T*k, E) routing
    # cumsum (measured 81-128 GiB/dev on kimi prefill_32k; ~12 GiB grouped).
    tokens = shape.global_batch * shape.seq_len
    groups = 1
    if cfg.num_experts:
        groups = max(plan.dp_size, tokens // 8192)
        while tokens % groups != 0 or groups % plan.dp_size != 0:
            groups -= 1
        groups = max(groups, plan.dp_size)

    def fn(params, batch):
        return tf.prefill_step(cfg, params, batch, moe_groups=groups)

    # explicit output shardings: without them GSPMD may replicate the
    # (batch, seq, kv, hd) caches over the data axes (measured 13.9 GiB/dev
    # on gemma2 prefill_32k vs 0.8 GiB sharded)
    out_specs = jax.eval_shape(fn, params_specs, specs)
    logits_sh = NamedSharding(plan.mesh, P(*( [tuple(plan.dp)] + [None] * (len(out_specs[0].shape) - 1))))
    cache_sh = shd.shardings_of(shd.cache_pspecs(out_specs[1], plan), plan)
    jitted = jax.jit(fn, in_shardings=(params_sh, batch_sh),
                     out_shardings=(logits_sh, cache_sh))
    return jitted, (params_specs, specs), {}


def build_decode(cfg, shape, plan, tuning):
    specs = input_specs(cfg, shape)
    tok_specs, cache_specs = specs["tokens"], specs["cache"]
    params_specs = tf.param_specs(cfg)
    params_sh = shd.shardings_of(shd.infer_pspecs(params_specs, plan), plan)
    cache_sh = shd.shardings_of(shd.cache_pspecs(cache_specs, plan), plan)
    b = tok_specs.shape[0]
    from repro.dist.sharding import _fit_axes  # divisibility-aware batch axis
    dp = _fit_axes(b, plan.dp, plan)
    tok_sh = NamedSharding(plan.mesh, P(dp, *([None] * (len(tok_specs.shape) - 1))))

    def fn(params, cache, tokens):
        return tf.decode_step(cfg, params, cache, tokens)

    jitted = jax.jit(
        fn, in_shardings=(params_sh, cache_sh, tok_sh),
        out_shardings=(None, cache_sh), donate_argnums=(1,),
    )
    return jitted, (params_specs, cache_specs, tok_specs), {}


def act_specs_for(cfg, plan, kind: str):
    """Activation sharding constraints installed during lowering.

    The residual carry of the layer scan is what remat saves per layer, so
    keeping it sharded over BOTH the dp axes (batch dim) and the tp axis
    (d_model dim) divides saved-activation HBM by dp*tp.

    Attention runs context-parallel (Ulysses-style): the q-block axis takes
    the tp axis and K/V are replicated within the layer — this sidesteps the
    head-count/16 divisibility problem (qwen2: 28 heads, internvl2: 14)."""
    dp = tuple(plan.dp)
    ep = tuple(plan.ep)
    moe = {
        # dispatch buffers (G,E,C,d): group-major before the EP boundary,
        # expert-major inside (forces the canonical all-to-all). d stays
        # unsharded: it is the contraction dim of the expert GEMMs — sharding
        # it over tp would turn every GEMM into partial-sum all-reduces.
        "moe_dispatch": P(None, ep, None, None),
        "moe_combine": P(dp, None, None, None),
    }
    if kind == "train":
        return {
            "residual": P(dp, None, plan.tp),
            "attn_q": P(dp, plan.tp, None, None, None),
            "attn_kv": P(dp, None, None, None),
            **moe,
        }
    if kind == "prefill":
        return {
            "attn_q": P(dp, plan.tp, None, None, None),
            "attn_kv": P(dp, None, None, None),
            **moe,
        }
    return moe


def active_params(cfg, specs) -> float:
    """Parameter count weighted by activation fraction (MoE experts count
    top_k/E) — the N in MODEL_FLOPS = 6*N*D."""
    total = 0.0
    for path, leaf in ptu.tree_flatten_with_paths(specs):
        import numpy as np

        n = float(np.prod(leaf.shape))
        if cfg.num_experts and (
            path.endswith("ffn/w_gate") or path.endswith("ffn/w_up")
            or path.endswith("ffn/w_out")
        ):
            n *= cfg.top_k / cfg.num_experts
        if path.endswith("embed/embedding"):
            continue  # lookup, not matmul
        total += n
    return total


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str | None,
             tuning_override: dict | None = None) -> dict:
    shape = SHAPES[shape_name]
    cfg = dryrun_config(arch)
    ok, why = cell_supported(arch, shape_name, cfg.causal)
    record: dict = {
        "arch": arch, "shape": shape_name,
        "mesh": "pod2x16x16" if multi_pod else "pod16x16",
        "kind": shape.kind,
    }
    if not ok:
        record.update(status="skipped", reason=why)
        _save(record, out_dir)
        return record

    mesh = make_production_mesh(multi_pod=multi_pod)
    params_specs = tf.param_specs(cfg)
    param_bytes = ptu.tree_bytes(params_specs)
    plan = make_plan(mesh, param_bytes=param_bytes)
    tuning = dict(CELL_TUNING.get((arch, shape_name), {}))
    if tuning_override:
        tuning.update(tuning_override)
    if "config" in tuning:
        cfg = cfg.replace(**tuning["config"])

    builders = {"train": build_train, "prefill": build_prefill, "decode": build_decode}
    t0 = time.time()
    try:
        with use_plan(plan, act_specs_for(cfg, plan, shape.kind)):
            jitted, args, info = builders[shape.kind](cfg, shape, plan, tuning)
            with mesh:
                lowered = jitted.lower(*args)
                compiled = lowered.compile()
        mem = compiled.memory_analysis()
        raw_cost = compiled.cost_analysis()
        hlo_text = compiled.as_text()
        prog = hlo_lib.HloProgram(hlo_text)
        analysis = prog.analyze()  # trip-count-aware, per-device
        upcast_live = prog.f32_upcast_live_bytes()
        chips = mesh.devices.size
        # memory term uses convert-adjusted traffic: the CPU backend emulates
        # bf16 matmuls via hoisted f32 copies that would not exist on TPU.
        terms = hlo_lib.roofline_terms(
            analysis["flops"], analysis["hbm_bytes_adjusted"],
            analysis["collectives"]["total_time_s"],
        )
        terms["memory_unadjusted_s"] = analysis["hbm_bytes"] / hlo_lib.HBM_BW
        # useful-compute ratio: MODEL_FLOPS vs compiled (per-device * chips)
        tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
        mf = hlo_lib.model_flops(
            active_params(cfg, params_specs), tokens,
            "train" if shape.kind == "train" else "infer",
        )
        hlo_global_flops = analysis["flops"] * chips
        record.update(
            status="ok",
            compile_s=round(time.time() - t0, 1),
            chips=chips,
            param_bytes=param_bytes,
            plan={"dp": plan.dp, "fsdp": plan.fsdp, "tp": plan.tp, "ep": plan.ep},
            tuning=info,
            memory={
                "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
                "output_bytes": getattr(mem, "output_size_in_bytes", 0),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
                "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", 0),
                "alias_bytes": getattr(mem, "alias_size_in_bytes", 0),
            },
            cost={
                "hlo_flops_per_device": analysis["flops"],
                "hlo_hbm_bytes_per_device": analysis["hbm_bytes"],
                "hlo_hbm_bytes_adjusted": analysis["hbm_bytes_adjusted"],
                "convert_bytes": analysis["convert_bytes"],
                "raw_cost_analysis_flops": float(raw_cost.get("flops", 0.0)) if raw_cost else 0.0,
                "model_flops_global": mf,
                "useful_flops_ratio": (mf / hlo_global_flops) if hlo_global_flops else 0.0,
            },
            collectives=analysis["collectives"],
            roofline=terms,
        )
        # per-device HBM occupancy (arguments are sharded; sizes reported by
        # memory_analysis are already per-device on SPMD executables).
        # adjusted = minus the CPU backend's hoisted f32 copies of bf16 data.
        arg_b = record["memory"]["argument_bytes"]
        tmp_b = record["memory"]["temp_bytes"]
        record["memory"]["f32_upcast_live_bytes"] = upcast_live
        record["memory"]["hbm_per_device_gib"] = round((arg_b + tmp_b) / GIB, 3)
        record["memory"]["hbm_per_device_adjusted_gib"] = round(
            (arg_b + max(tmp_b - upcast_live, 0)) / GIB, 3
        )
        log.info(
            "%s x %s [%s]: compile %.1fs, %.2f GiB/dev (adj %.2f), dominant=%s",
            arch, shape_name, record["mesh"], record["compile_s"],
            record["memory"]["hbm_per_device_gib"],
            record["memory"]["hbm_per_device_adjusted_gib"], terms["dominant"],
        )
    except Exception as e:  # noqa: BLE001 — a failed cell is a recorded bug
        record.update(status="error", error=f"{type(e).__name__}: {e}",
                      traceback=traceback.format_exc()[-2000:])
        log.error("%s x %s FAILED: %s", arch, shape_name, record["error"])
    _save(record, out_dir)
    return record


def _save(record: dict, out_dir: str | None):
    if not out_dir:
        return
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{record['arch']}__{record['shape']}__{record['mesh']}.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=1, default=str)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="runs/dryrun")
    ap.add_argument("--num-micro", type=int, default=None)
    args = ap.parse_args()

    meshes = [args.multi_pod] if not args.both_meshes else [False, True]
    tuning = {"num_micro": args.num_micro} if args.num_micro else None
    cells = []
    if args.all:
        for arch in ARCH_NAMES:
            for shape in SHAPES:
                cells.append((arch, shape))
    else:
        if not (args.arch and args.shape):
            ap.error("--arch and --shape required unless --all")
        cells.append((args.arch, args.shape))

    failures = 0
    for multi in meshes:
        for arch, shape in cells:
            rec = run_cell(arch, shape, multi, args.out, tuning)
            if rec["status"] == "ok":
                print(f"OK   {arch} x {shape} [{rec['mesh']}] "
                      f"{rec['memory']['hbm_per_device_gib']} GiB/dev "
                      f"dominant={rec['roofline']['dominant']}")
                print("  memory:", rec["memory"])
                print("  cost:", rec["cost"])
            elif rec["status"] == "skipped":
                print(f"SKIP {arch} x {shape} [{rec['mesh']}]: {rec['reason']}")
            else:
                failures += 1
                print(f"FAIL {arch} x {shape} [{rec['mesh']}]: {rec['error']}")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
