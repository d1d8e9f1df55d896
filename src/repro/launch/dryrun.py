# ---------------------------------------------------------------------------
# Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell against
# the production mesh, prove it fits, and extract the roofline terms.
#
#   PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b \
#       --shape train_4k --mesh pod1 --out benchmarks/results/dryrun
#
# main() pins the process to 512 placeholder host devices before JAX
# initialises a backend (jax locks the device count on first init); they
# back both the single-pod (16,16) and multi-pod (2,16,16) meshes.  The
# dry-run therefore never takes an accelerator, even on a machine with one.
# ---------------------------------------------------------------------------
import argparse
import dataclasses
import json
import os
import re
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import SHAPES, get_config, shape_applicable
from ..core.transprecision import get_policy
from ..models.common import axis_rules
from ..models.lm import ModelCfg
from ..models.serve_model import decode_step, prefill
from ..optim import AdamWConfig
from ..train.step import init_train_state, make_train_step, state_specs
from . import hlo_cost
from . import mesh as mesh_lib
from .specs import decode_specs, input_specs

# v5e-class hardware constants (per chip) for the roofline terms
PEAK_FLOPS = 197e12          # bf16 FLOP/s
HBM_BW = 819e9               # bytes/s
ICI_BW = 50e9                # bytes/s/link
HBM_CAP = 16e9               # bytes


# ---------------------------------------------------------------------------
# Collective parsing from post-SPMD HLO
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "pred": 1, "s4": 0.5, "u4": 0.5, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1,
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLL_KINDS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


def _shape_bytes(txt: str) -> int:
    """Sum bytes over every typed shape literal in ``txt``."""
    total = 0.0
    for m in _SHAPE_RE.finditer(txt):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return int(total)


def parse_collectives(hlo_text: str) -> Dict[str, Any]:
    """Per-collective-op byte accounting from the per-device HLO module.

    For each op we take the *result* shape bytes (for all-gather this is the
    gathered tensor ~= wire bytes in+out per device; for all-reduce /
    reduce-scatter / all-to-all / collective-permute the operand and result
    describe the same payload).  ``operand_bytes`` (the spec's "sum of
    operand sizes") is also recorded from the inline-typed operands.
    """
    per_kind = {k: {"count": 0, "result_bytes": 0, "operand_bytes": 0}
                for k in _COLL_KINDS}
    for line in hlo_text.splitlines():
        ls = line.strip()
        m = re.match(r"(?:ROOT )?%?[\w.\-]+ = (.*)", ls)
        if not m:
            continue
        rhs = m.group(1)
        kind = None
        for k in _COLL_KINDS:
            if re.search(rf"\b{k}(?:-start|-done)?\(", rhs):
                kind = k
                break
        if kind is None:
            continue
        if f"{kind}-done(" in rhs:      # avoid double counting async pairs
            continue
        lhs, _, call = rhs.partition(f" {kind}")
        per_kind[kind]["count"] += 1
        per_kind[kind]["result_bytes"] += _shape_bytes(lhs)
        inner = call[call.find("(") + 1: call.rfind(")")] if call else ""
        per_kind[kind]["operand_bytes"] += _shape_bytes(inner)
    total_result = sum(v["result_bytes"] for v in per_kind.values())
    total_operand = sum(v["operand_bytes"] for v in per_kind.values())
    return {"per_kind": per_kind, "result_bytes": total_result,
            "operand_bytes": total_operand}


def hlo_op_histogram(hlo_text: str, top: int = 12) -> Dict[str, int]:
    ops: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \S+ ([\w\-]+)\(", line)
        if m:
            ops[m.group(1)] = ops.get(m.group(1), 0) + 1
    return dict(sorted(ops.items(), key=lambda kv: -kv[1])[:top])


# ---------------------------------------------------------------------------
# Model-FLOPs accounting (6ND / 2ND with MoE active-param scaling)
# ---------------------------------------------------------------------------

def active_params(cfg: ModelCfg) -> Dict[str, float]:
    from ..models.lm import init_params
    p = init_params(jax.random.PRNGKey(0), cfg, abstract=True)
    total = active = 0.0
    for kp, leaf in jax.tree_util.tree_flatten_with_path(p)[0]:
        path = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in kp)
        n = float(np.prod(leaf.shape))
        total += n
        if "moe" in path and path.split("/")[-1] in ("wi", "wo"):
            active += n * cfg.moe_topk / cfg.moe_experts
        else:
            active += n
    return {"total": total, "active": active}


def model_flops(cfg: ModelCfg, kind: str, batch: int, seq: int,
                n_active: float) -> float:
    tokens = batch * (seq if kind in ("train", "prefill") else 1)
    mult = 6.0 if kind == "train" else 2.0
    return mult * n_active * tokens


# ---------------------------------------------------------------------------
# Cell lowering
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Variant:
    """Hillclimb knobs (defaults = baseline).

    The baseline is the production sharding: FSDP(data) x TP(model) with
    sequence-parallel residuals and head-sharded attention — the weakest
    configs that still FIT 16 GB/chip (seq/heads sharding off blows HBM at
    train_4k; see EXPERIMENTS.md §Dry-run)."""
    policy: str = "bf16"
    seq_shard: bool = True          # sequence-parallel residual stream
    heads_shard: bool = True        # shard attention heads on "model"
    remat: Optional[str] = None     # override cfg.remat
    scan_layers: Optional[bool] = None
    distributed_decode: bool = False  # shard_map LSE decode attention
    q_block: Optional[int] = None
    kv_block: Optional[int] = None
    attn_vjp: Optional[str] = None    # flash | naive
    packed: bool = False              # posit-packed weights/KV (serving)

    def apply(self, cfg: ModelCfg) -> ModelCfg:
        kw = {}
        if self.remat is not None:
            kw["remat"] = self.remat
        if self.scan_layers is not None:
            kw["scan_layers"] = self.scan_layers
        if self.q_block:
            kw["q_block"] = self.q_block
        if self.kv_block:
            kw["kv_block"] = self.kv_block
        if self.attn_vjp:
            kw["attn_vjp"] = self.attn_vjp
        return dataclasses.replace(cfg, **kw) if kw else cfg


def lower_cell(arch: str, shape: str, multi_pod: bool,
               variant: Variant = Variant()):
    """Lower + compile one (arch x shape x mesh) cell; return report dict."""
    cfg = variant.apply(get_config(arch))
    spec = SHAPES[shape]
    policy = get_policy(variant.policy)
    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(list(mesh.shape.values())))
    t0 = time.time()

    if spec.kind == "train":
        rules = mesh_lib.train_rules(mesh, global_batch=spec.global_batch,
                                     seq_shard=variant.seq_shard,
                                     heads_shard=variant.heads_shard)
    else:
        rules = mesh_lib.serve_rules(mesh, global_batch=spec.global_batch)

    opt_cfg = AdamWConfig()
    with mesh, axis_rules(rules):
        abstract_params = jax.eval_shape(
            lambda: init_train_state(jax.random.PRNGKey(0), cfg, opt_cfg,
                                     policy).params)
        if variant.packed and spec.kind != "train":
            # posit-packed serving weights (decode-on-load)
            from ..core.transprecision import pack_params
            abstract_params = pack_params(abstract_params, policy,
                                          abstract=True)
        fsdp = "data" if spec.kind == "train" else None
        pspecs = mesh_lib.param_specs(abstract_params, fsdp=fsdp)
        psh = mesh_lib.to_shardings(mesh, pspecs)

        if spec.kind == "train":
            state_abs = init_train_state(jax.random.PRNGKey(0), cfg, opt_cfg,
                                         policy, abstract=True)
            ssh = mesh_lib.to_shardings(
                mesh, state_specs(cfg, pspecs, policy))
            bsh = mesh_lib.to_shardings(
                mesh, mesh_lib.batch_specs(cfg, rules))
            step = make_train_step(cfg, opt_cfg, policy)
            jitted = jax.jit(step, in_shardings=(ssh, bsh),
                             out_shardings=(ssh, None), donate_argnums=0)
            lowered = jitted.lower(state_abs, input_specs(cfg, spec))
        elif spec.kind == "prefill":
            batch = input_specs(cfg, spec)
            bsh = mesh_lib.to_shardings(
                mesh, mesh_lib.batch_specs(cfg, rules, keys=set(batch)))

            def prefill_fn(params, b):
                return prefill(params, b, cfg, spec.seq_len, policy)

            jitted = jax.jit(prefill_fn, in_shardings=(psh, bsh))
            lowered = jitted.lower(abstract_params, batch)
        else:  # decode
            cache_abs, tok = decode_specs(cfg, spec, policy)
            csh = mesh_lib.to_shardings(
                mesh, mesh_lib.cache_specs(cache_abs, cfg, rules))
            tok_sh = mesh_lib.to_shardings(
                mesh, jax.sharding.PartitionSpec(rules.get("batch"), None)
                if cfg.family != "vlm" else
                jax.sharding.PartitionSpec(rules.get("batch"), None, None))
            if variant.distributed_decode:
                from ..serve.distributed import make_distributed_decode_step
                step = make_distributed_decode_step(cfg, policy, mesh, rules)
            else:
                def step(params, cache, tok):
                    if cfg.family == "vlm":
                        return decode_step(params, cache, None, cfg, policy,
                                           embeds=tok)
                    return decode_step(params, cache, tok, cfg, policy)
            jitted = jax.jit(step, in_shardings=(psh, csh, tok_sh),
                             out_shardings=None, donate_argnums=1)
            lowered = jitted.lower(abstract_params, cache_abs, tok)

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    # ---- extract analysis ----
    # NOTE: compiled.cost_analysis() counts while-loop bodies ONCE on this
    # backend (verified: scan(4) == scan(8)); the production programs are
    # scan-over-layers, so the roofline terms come from the loop-aware HLO
    # parser (hlo_cost.analyze — trip counts from known_trip_count), which
    # matches cost_analysis exactly on loop-free modules (tested).
    xla_cost = compiled.cost_analysis() or {}
    mem = compiled.memory_analysis()
    mem_d: Dict[str, Any] = {}
    for f in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        v = getattr(mem, f, None)
        if v is not None:
            mem_d[f] = int(v)
    hlo = compiled.as_text()
    cost = hlo_cost.analyze(hlo)
    coll = parse_collectives(hlo)   # single-instance per-kind cross-check
    np_info = active_params(cfg)
    flops = float(cost["flops"])
    bytes_acc = float(cost["bytes"])
    coll_bytes = float(cost["collective_bytes"])

    # roofline terms (per-device quantities vs per-chip peaks)
    t_compute = flops / PEAK_FLOPS
    t_memory = bytes_acc / HBM_BW
    t_coll = coll_bytes / ICI_BW
    dominant = max((("compute", t_compute), ("memory", t_memory),
                    ("collective", t_coll)), key=lambda kv: kv[1])[0]
    mf = model_flops(cfg, spec.kind, spec.global_batch, spec.seq_len,
                     np_info["active"])
    mf_per_dev = mf / n_chips

    report = {
        "arch": arch, "shape": shape,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": n_chips, "kind": spec.kind,
        "variant": dataclasses.asdict(variant),
        "params_total": np_info["total"], "params_active": np_info["active"],
        "xla_cost_analysis_loopbody_once": {
            k: float(v) for k, v in xla_cost.items()
            if isinstance(v, (int, float)) and k in ("flops", "bytes accessed")
        },
        "hlo_cost": {"flops": flops, "bytes": bytes_acc,
                     "collectives": cost["collectives"]},
        "memory_analysis": mem_d,
        "collectives_single_instance": coll,
        "hlo_ops": hlo_op_histogram(hlo),
        "roofline": {
            "flops_per_device": flops,
            "hbm_bytes_per_device": bytes_acc,
            "collective_bytes_per_device": coll_bytes,
            "t_compute_s": t_compute, "t_memory_s": t_memory,
            "t_collective_s": t_coll, "dominant": dominant,
            "model_flops_global": mf,
            "model_flops_per_device": mf_per_dev,
            "useful_flops_ratio": (mf_per_dev / flops) if flops else 0.0,
            "roofline_fraction": (mf_per_dev / PEAK_FLOPS)
            / max(t_compute, t_memory, t_coll)
            if max(t_compute, t_memory, t_coll) > 0 else 0.0,
        },
        "fits_hbm": mem_d.get("temp_size_in_bytes", 0)
        + mem_d.get("argument_size_in_bytes", 0) <= HBM_CAP,
        "timings": {"lower_s": t_lower, "compile_s": t_compile},
    }
    return report


HOST_DEVICES = 512


def _use_host_devices():
    """Select the CPU backend with ``HOST_DEVICES`` placeholder devices.
    Must run before anything touches a JAX backend."""
    os.environ["XLA_FLAGS"] = " ".join(filter(None, [
        os.environ.get("XLA_FLAGS", ""),
        f"--xla_force_host_platform_device_count={HOST_DEVICES}"]))
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")


def main():
    _use_host_devices()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=list(SHAPES))
    ap.add_argument("--mesh", default="pod1", choices=["pod1", "pod2"])
    ap.add_argument("--policy", default="bf16")
    ap.add_argument("--no-seq-shard", action="store_true")
    ap.add_argument("--no-heads-shard", action="store_true")
    ap.add_argument("--remat", default=None)
    ap.add_argument("--no-scan", action="store_true")
    ap.add_argument("--distributed-decode", action="store_true")
    ap.add_argument("--q-block", type=int, default=0)
    ap.add_argument("--kv-block", type=int, default=0)
    ap.add_argument("--attn-vjp", default=None, choices=["flash", "naive"])
    ap.add_argument("--packed", action="store_true",
                    help="posit-packed weights/KV for serve cells")
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default="benchmarks/results/dryrun")
    args = ap.parse_args()

    ok, why = shape_applicable(args.arch, args.shape)
    name = f"{args.arch}_{args.shape}_{args.mesh}" + (
        f"_{args.tag}" if args.tag else "")
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, name + ".json")
    if not ok:
        json.dump({"arch": args.arch, "shape": args.shape, "mesh": args.mesh,
                   "skipped": True, "reason": why}, open(path, "w"), indent=1)
        print(f"SKIP {name}: {why}")
        return

    variant = Variant(
        policy=args.policy, seq_shard=not args.no_seq_shard,
        heads_shard=not args.no_heads_shard, remat=args.remat,
        scan_layers=False if args.no_scan else None,
        distributed_decode=args.distributed_decode,
        q_block=args.q_block, kv_block=args.kv_block,
        attn_vjp=args.attn_vjp, packed=args.packed)
    report = lower_cell(args.arch, args.shape, args.mesh == "pod2", variant)
    report["tag"] = args.tag
    json.dump(report, open(path, "w"), indent=1)
    r = report["roofline"]
    print(f"OK {name}: dominant={r['dominant']} "
          f"compute={r['t_compute_s']:.4f}s memory={r['t_memory_s']:.4f}s "
          f"collective={r['t_collective_s']:.4f}s "
          f"frac={r['roofline_fraction']:.3f} "
          f"mem={report['memory_analysis']} "
          f"compile={report['timings']['compile_s']:.0f}s")


if __name__ == "__main__":
    main()
