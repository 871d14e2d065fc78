_DOC = """Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this script:

1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
2. constructs ShapeDtypeStruct stand-ins for params, optimizer state,
   caches and inputs (zero device allocation),
3. ``jax.jit(step).lower(...).compile()`` under the mesh with the
   framework's shardings,
4. records ``memory_analysis()`` / ``cost_analysis()`` and parses the
   post-SPMD HLO for collective operand bytes,
5. derives the three roofline terms (see EXPERIMENTS.md §Roofline).

XLA's cost analysis counts while-loop (scan) bodies ONCE, so naive totals
under-count by the layer count.  Two corrections are applied:

* FLOPs/bytes — *probe extrapolation*: the same cell is lowered at depth
  L=1 and L=2 (with chunk scans disabled so nested attention/SSD loops are
  fully counted); per-layer cost = f(2) - f(1), outside-cost = f(1) -
  per-layer, total = outside + L * per-layer.  Probes reuse the cell's
  width/shape/sharding, so per-device partitioning matches.
* collectives — ops whose HLO metadata places them inside while bodies are
  multiplied by the known scan trip counts (layer count; group/inner
  counts for the hybrid arch).

Usage:
  python -m repro.launch.dryrun --arch yi-6b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --out results/dryrun.json
"""
__doc__ = _DOC

import argparse
import dataclasses
import json
import os
import re
import time
import traceback
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs import get_config, list_archs, shapes_for
from repro.configs.base import (
    ApproxConfig,
    Backend,
    Family,
    ModelConfig,
    ShapeConfig,
    StepKind,
    TrainConfig,
    TrainMode,
)
from repro.launch.mesh import (
    HBM_BW,
    ICI_BW_PER_LINK,
    PEAK_FLOPS_BF16,
    make_production_mesh,
)
from repro.models import build_model
from repro.runtime import sharding as shard_lib
from repro.training import steps as step_lib


# ---------------------------------------------------------------------------
# Per-arch training policy (memory knobs — see EXPERIMENTS.md §Perf)
# ---------------------------------------------------------------------------


def train_config_for(cfg: ModelConfig, probe: bool = False, **overrides) -> TrainConfig:
    big = cfg.param_count() > 10e9
    kw = dict(
        microbatches=1,
        remat="block",
        fsdp=big,
        chunk_q=1 << 30 if probe else 1024,  # probes: no chunk scan
        scan_unroll=probe,                   # probes: unroll layer scans so
    )                                        # cost analysis counts them fully
    kw.update(overrides)
    return TrainConfig(**kw)


def approx_config_for(
    kind: StepKind, mode: str, backend: str = "analog"
) -> ApproxConfig:
    """Dry-run approx policy: training integrates the paper's technique
    (INJECT on ``backend`` — the headline cheap-forward case); serving
    cells are exact by default (inference executes on the approximate
    hardware itself, not the TPU).  Exception: ``mode="model"`` requests
    bit-accurate emulation of ``backend`` on any cell kind — this is how
    the roofline benchmark lowers the emulated decode hot path the fused
    kernels target.  ``mode`` overrides: exact | inject | model."""
    if mode == "exact":
        return ApproxConfig()
    if mode == "model":
        return ApproxConfig(backend=Backend(backend), mode=TrainMode.MODEL)
    if kind != StepKind.TRAIN:
        return ApproxConfig()
    return ApproxConfig(backend=Backend(backend), mode=TrainMode.INJECT)


def probe_depths(cfg: ModelConfig) -> Tuple[ModelConfig, ModelConfig, int]:
    """Depth-1 / depth-2 probe configs + the extrapolation count.

    For hybrid archs the scanned unit is a *group* (k mamba layers + the
    shared attn block), so probes are 1 and 2 groups and the count is G;
    the tail (n_layers % k) is folded in as a fractional group —
    documented approximation, < 3% of depth for the assigned config.
    """
    if cfg.family == Family.HYBRID:
        k = cfg.shared_attn_every
        G = cfg.n_layers // k
        c1 = dataclasses.replace(cfg, n_layers=k)
        c2 = dataclasses.replace(cfg, n_layers=2 * k)
        return c1, c2, G
    big_chunk = dataclasses.replace(cfg, ssm_chunk=1 << 30) if cfg.ssm_state else cfg
    c1 = dataclasses.replace(big_chunk, n_layers=1)
    c2 = dataclasses.replace(big_chunk, n_layers=2)
    return c1, c2, cfg.n_layers


def _probe_ssm_chunk(cfg: ModelConfig, seq_len: int) -> int:
    # cap the probe SSD chunk so the [l, l] intra-chunk tensors stay sane
    return min(seq_len, 4096)


# ---------------------------------------------------------------------------
# HLO collective parsing
# ---------------------------------------------------------------------------

_COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1,
    "u64": 8, "u32": 4, "u16": 2, "u8": 1, "pred": 1,
}

_SHAPE_RE = re.compile(
    r"\b(f64|f32|f16|bf16|s64|s32|s16|s8|u64|u32|u16|u8|pred)\[([0-9,]*)\]"
)
_OPNAME_RE = re.compile(r'op_name="([^"]*)"')


def _result_bytes(line: str, op_kind: str) -> int:
    """Sum the bytes of the result type(s) of an HLO op line.

    HLO format: ``%name = <result-type(s)> op-kind(operands), ...`` — the
    result type(s) sit between '=' and the op-kind token.
    """
    rhs = line.split("=", 1)[1]
    cut = rhs.find(f" {op_kind}")
    if cut >= 0:
        rhs = rhs[:cut]
    total = 0
    for m in _SHAPE_RE.finditer(rhs):
        n = 1
        for d in m.group(2).split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[m.group(1)]
    return total


def collective_bytes_from_hlo(hlo: str, level_mults: List[int]) -> Dict[str, Any]:
    """Sum collective result bytes.  An op whose metadata op_name contains
    N ``while/body`` segments executes inside N nested scans; its bytes are
    multiplied by prod(level_mults[:N])."""
    per_kind = {k: 0 for k in _COLLECTIVES}
    total = 0
    for line in hlo.splitlines():
        s = line.strip()
        if "=" not in s:
            continue
        op = s.split("=", 1)[1]
        op = op.split("metadata", 1)[0]  # never match inside op_name strings
        kind_hit = None
        for kind in _COLLECTIVES:
            if f" {kind}(" in op or f" {kind}-start(" in op:
                kind_hit = kind
                break
        if kind_hit is None:
            continue
        m = _OPNAME_RE.search(s)
        depth = m.group(1).count("while/body") if m else 0
        mult = 1
        for lv in range(min(depth, len(level_mults))):
            mult *= level_mults[lv]
        b = _result_bytes(s, kind_hit) * mult
        per_kind[kind_hit] += b
        total += b
    return {"total": total, "per_kind": per_kind}


def level_mults_for(cfg: ModelConfig, tcfg: TrainConfig) -> List[int]:
    """Scan trip counts by nesting level.

    Outermost level is the microbatch accumulation scan (when >1), then
    the scan over layers (groups for hybrid), then hybrid inner mamba
    scans — attention/SSD chunk scans contain no collectives under
    head-sharded attention (verified on the lowered HLO)."""
    if cfg.family == Family.HYBRID:
        G = cfg.n_layers // cfg.shared_attn_every
        levels = [G, cfg.shared_attn_every]
    else:
        levels = [cfg.n_layers, 1]
    if tcfg.microbatches > 1:
        levels = [tcfg.microbatches] + levels
    return levels


# ---------------------------------------------------------------------------
# Cell lowering
# ---------------------------------------------------------------------------


def lower_cell(
    cfg: ModelConfig,
    shape: ShapeConfig,
    mesh,
    tcfg: TrainConfig,
    approx: ApproxConfig,
    fused: bool = False,
):
    """Lower one (config, shape) under a mesh; returns the jax Lowered.

    ``fused`` applies to emulated DECODE cells only: it routes MODEL-mode
    projections through the backends' fused epilogue kernels and cache
    attention through the flash decode kernel (the serving hot path), so
    the roofline benchmark can lower both variants of the same cell.
    """
    model = build_model(cfg)
    if shape.kind == StepKind.TRAIN:
        state_sds = jax.eval_shape(
            lambda: step_lib.init_train_state(model, jax.random.PRNGKey(0), approx)
        )
        state_sh = {
            "params": shard_lib.params_shardings(state_sds["params"], mesh, tcfg.fsdp),
            "opt": {
                "m": shard_lib.params_shardings(state_sds["opt"]["m"], mesh, True),
                "v": shard_lib.params_shardings(state_sds["opt"]["v"], mesh, True),
                "master": shard_lib.params_shardings(state_sds["opt"]["master"], mesh, True),
                "count": shard_lib.replicated(mesh),
            },
            "calib": jax.tree_util.tree_map(
                lambda _: shard_lib.replicated(mesh), state_sds["calib"]
            ),
            "step": shard_lib.replicated(mesh),
        }
        batch_sds = model.input_specs(shape.global_batch, shape.seq_len)
        batch_sh = jax.tree_util.tree_map(
            lambda s: jax.NamedSharding(mesh, shard_lib.batch_spec(s.shape, mesh)),
            batch_sds,
        )
        rng_sds = jax.ShapeDtypeStruct((2,), jnp.uint32)
        step_fn = step_lib.make_train_step(model, approx, tcfg)
        with jax.set_mesh(mesh):
            return jax.jit(
                step_fn,
                in_shardings=(state_sh, batch_sh, shard_lib.replicated(mesh)),
                donate_argnums=(0,),
            ).lower(state_sds, batch_sds, rng_sds)

    if shape.kind == StepKind.PREFILL:
        model_ = model
        params_sds = jax.eval_shape(lambda: model_.init(jax.random.PRNGKey(0)))
        params_sh = shard_lib.params_shardings(params_sds, mesh, tcfg.fsdp)
        batch_sds = model.input_specs(shape.global_batch, shape.seq_len)
        batch_sds.pop("labels")
        batch_sh = jax.tree_util.tree_map(
            lambda s: jax.NamedSharding(mesh, shard_lib.batch_spec(s.shape, mesh)),
            batch_sds,
        )

        def prefill(params, batch):
            out = model_.apply(
                params, batch, remat="block", chunk_q=tcfg.chunk_q,
                unroll=tcfg.scan_unroll,
            )
            return out.logits[:, -1]

        with jax.set_mesh(mesh):
            return jax.jit(prefill, in_shardings=(params_sh, batch_sh)).lower(
                params_sds, batch_sds
            )

    # DECODE
    params_sds = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    params_sh = shard_lib.params_shardings(params_sds, mesh, False)
    cache_sds = jax.eval_shape(
        lambda: model.init_cache(shape.global_batch, shape.seq_len)
    )
    cache_sh = jax.tree_util.tree_map(
        lambda s: jax.NamedSharding(
            mesh,
            shard_lib.cache_spec(s.shape, mesh)
            if s.ndim >= 4
            else shard_lib.batch_spec((1,) + tuple(s.shape[1:]), mesh),
        ),
        cache_sds,
    )
    tok_sds = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    tok_sh = jax.NamedSharding(mesh, shard_lib.batch_spec(tok_sds.shape, mesh))
    pos_sds = jax.ShapeDtypeStruct((), jnp.int32)

    ctx = None
    if approx.active:
        from repro.core.approx_linear import ApproxCtx

        ctx = ApproxCtx(cfg=approx, rng=jax.random.PRNGKey(0), fused=fused)

    def decode(params, cache, tokens, pos):
        return model.serve_step(
            params, cache, tokens, pos, unroll=tcfg.scan_unroll,
            ctx=ctx, flash=fused,
        )

    with jax.set_mesh(mesh):
        return jax.jit(
            decode,
            in_shardings=(params_sh, cache_sh, tok_sh, shard_lib.replicated(mesh)),
            donate_argnums=(1,),
        ).lower(params_sds, cache_sds, tok_sds, pos_sds)


def _cost(compiled) -> Tuple[float, float]:
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):  # older jax wraps it per-computation
        cost = cost[0] if cost else {}
    return float(cost.get("flops", 0.0)), float(cost.get("bytes accessed", 0.0))


# ---------------------------------------------------------------------------
# Cell result
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class CellResult:
    arch: str
    shape: str
    mesh: str
    kind: str
    approx: str
    fused: bool = False
    ok: bool = False
    error: Optional[str] = None
    compile_s: float = 0.0
    flops: float = 0.0              # per-device, probe-extrapolated
    bytes_accessed: float = 0.0     # per-device, probe-extrapolated
    collective_bytes: float = 0.0   # per-device, trip-count multiplied
    collective_detail: Optional[Dict] = None
    memory: Optional[Dict] = None
    model_flops: float = 0.0        # global analytic 6·N·D / 2·N·D
    params: float = 0.0
    roofline: Optional[Dict] = None


def per_site_macs(
    cfg: ModelConfig, seq_len: int = 1, batch: int = 1
) -> Dict[str, Dict[str, float]]:
    """Analytic MAC counts per ``dense()`` call-site for one forward pass.

    Returns ``{site: {"macs": total MACs over batch*seq_len tokens,
    "k": contraction dim, "bwd_macs": backward-pass MACs}}`` — the
    per-site FLOP breakdown the approximation-search cost model
    (repro.search.costmodel) prices in joules-equivalents.  ``bwd_macs``
    is 2x the forward count: each projection's backward is two matmuls of
    the forward's MAC count (dL/dx = g @ w.T and dL/dW = x.T @ g) — the
    quantity the gated approximate backward (repro.core.injection) moves
    onto the int8 datapath, priced by ``costmodel.backward_map_energy``.
    Only projection sites are counted (the QK^T/AV einsums and SSD
    recurrence are not ``dense()`` sites and stay on the host
    accelerator, not the approximate hardware).  MoE sites count the
    top-k *active* experts per token; the SSM in-projection width is the
    unpadded ``2*d_in + 2*N + H`` (REPRO_SSM_PAD adds dead columns that
    carry no useful MACs).
    """
    d, f = cfg.d_model, cfg.d_ff
    h, kv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    tokens = float(seq_len * batch)

    attn = {
        "attn_q": (d, h * dh),
        "attn_k": (d, kv * dh),
        "attn_v": (d, kv * dh),
        "attn_o": (h * dh, d),
    }
    mlp = {"mlp_gate": (d, f), "mlp_up": (d, f), "mlp_down": (f, d)}
    d_in, H, N = cfg.ssm_d_inner, cfg.ssm_n_heads, cfg.ssm_state
    ssm = {"ssm_in": (d, 2 * d_in + 2 * N + H), "ssm_out": (d_in, d)}

    out: Dict[str, Dict[str, float]] = {}

    def add(site: str, k: int, n: int, copies: float) -> None:
        if k <= 0 or n <= 0 or copies <= 0:
            return
        entry = out.setdefault(
            site, {"macs": 0.0, "bwd_macs": 0.0, "k": float(k)}
        )
        macs = tokens * float(k) * float(n) * float(copies)
        entry["macs"] += macs
        entry["bwd_macs"] += 2.0 * macs

    if cfg.family == Family.SSM:
        for site, (k, n) in ssm.items():
            add(site, k, n, cfg.n_layers)
    elif cfg.family == Family.HYBRID:
        G = cfg.n_layers // cfg.shared_attn_every
        for site, (k, n) in ssm.items():
            add(site, k, n, cfg.n_layers)   # groups + tail = n_layers mixers
        for site, (k, n) in attn.items():
            add(site, k, n, G)              # shared block applied per group
        for site, (k, n) in mlp.items():
            add(site, k, n, G)
    else:  # DENSE / MOE / VLM / AUDIO
        for site, (k, n) in attn.items():
            add(site, k, n, cfg.n_layers)
        if cfg.n_experts:
            add("moe_router", d, cfg.n_experts, cfg.n_layers)
            add("moe_gate", d, f, cfg.n_layers * cfg.top_k)
            add("moe_up", d, f, cfg.n_layers * cfg.top_k)
            add("moe_down", f, d, cfg.n_layers * cfg.top_k)
        else:
            for site, (k, n) in mlp.items():
                add(site, k, n, cfg.n_layers)
    add("lm_head", d, cfg.vocab_size, 1)
    return out


def model_flops_for(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """6·N_active·D for train, 2·N_active·D for forward/decode tokens."""
    n_active = cfg.active_param_count()
    if shape.kind == StepKind.TRAIN:
        return 6.0 * n_active * shape.seq_len * shape.global_batch
    if shape.kind == StepKind.PREFILL:
        return 2.0 * n_active * shape.seq_len * shape.global_batch
    return 2.0 * n_active * shape.global_batch


def run_cell(
    arch: str,
    shape: ShapeConfig,
    multi_pod: bool,
    approx_mode: str = "inject",
    verbose: bool = True,
    probes: bool = True,
    backend: str = "analog",
    fused: bool = False,
    **tcfg_overrides,
) -> CellResult:
    cfg = get_config(arch)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    approx = approx_config_for(shape.kind, approx_mode, backend)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    res = CellResult(
        arch=arch, shape=shape.name, mesh=mesh_name, kind=shape.kind.value,
        approx=(approx.backend.value + "/" + approx.mode.value) if approx.active else "exact",
        fused=fused,
    )
    try:
        tcfg = train_config_for(cfg, **tcfg_overrides)
        t0 = time.perf_counter()
        lowered = lower_cell(cfg, shape, mesh, tcfg, approx, fused=fused)
        compiled = lowered.compile()
        res.compile_s = time.perf_counter() - t0

        mem = compiled.memory_analysis()
        if mem is not None:
            res.memory = {
                k: float(getattr(mem, k))
                for k in (
                    "argument_size_in_bytes",
                    "output_size_in_bytes",
                    "temp_size_in_bytes",
                    "alias_size_in_bytes",
                )
                if hasattr(mem, k)
            }
        hlo = compiled.as_text()
        coll = collective_bytes_from_hlo(hlo, level_mults_for(cfg, tcfg))
        res.collective_bytes = float(coll["total"])
        res.collective_detail = coll["per_kind"]

        # ---- probe extrapolation for flops/bytes ----------------------
        if probes:
            c1, c2, count = probe_depths(cfg)
            if cfg.ssm_state:
                c1 = dataclasses.replace(c1, ssm_chunk=_probe_ssm_chunk(cfg, shape.seq_len))
                c2 = dataclasses.replace(c2, ssm_chunk=_probe_ssm_chunk(cfg, shape.seq_len))
            ptcfg = train_config_for(cfg, probe=True, **tcfg_overrides)
            f1, b1 = _cost(lower_cell(c1, shape, mesh, ptcfg, approx).compile())
            f2, b2 = _cost(lower_cell(c2, shape, mesh, ptcfg, approx).compile())
            per_layer_f, per_layer_b = f2 - f1, b2 - b1
            res.flops = (f1 - per_layer_f) + count * per_layer_f
            res.bytes_accessed = (b1 - per_layer_b) + count * per_layer_b
        else:
            res.flops, res.bytes_accessed = _cost(compiled)

        res.params = float(cfg.param_count())
        res.model_flops = model_flops_for(cfg, shape)
        compute_t = res.flops / PEAK_FLOPS_BF16
        memory_t = res.bytes_accessed / HBM_BW
        coll_t = res.collective_bytes / ICI_BW_PER_LINK
        dominant = max(
            ("compute", compute_t), ("memory", memory_t), ("collective", coll_t),
            key=lambda kv: kv[1],
        )[0]
        res.roofline = {
            "compute_s": compute_t,
            "memory_s": memory_t,
            "collective_s": coll_t,
            "dominant": dominant,
            "model_flops_ratio": res.model_flops / max(res.flops * n_chips, 1.0),
            "chips": n_chips,
        }
        res.ok = True
        if verbose:
            print(
                f"[dryrun] {arch} {shape.name} {mesh_name} OK "
                f"compile={res.compile_s:.1f}s flops/dev={res.flops:.3e} "
                f"bytes/dev={res.bytes_accessed:.3e} coll/dev={res.collective_bytes:.3e} "
                f"dominant={dominant} useful={res.roofline['model_flops_ratio']:.2f}",
                flush=True,
            )
    except Exception as e:  # noqa: BLE001 — each cell reports independently
        res.ok = False
        res.error = f"{type(e).__name__}: {e}\n{traceback.format_exc()[-2000:]}"
        if verbose:
            print(
                f"[dryrun] {arch} {shape.name} {mesh_name} FAILED: "
                f"{type(e).__name__}: {e}",
                flush=True,
            )
    return res


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def force_host_devices() -> None:
    """Give the CPU backend the fake devices a production mesh needs
    (``REPRO_DRYRUN_XLA_FLAGS``, default 512).  Takes effect only before
    JAX's first backend initialization, so entry points call it first
    thing; importing this module changes nothing."""
    os.environ["XLA_FLAGS"] = os.environ.get(
        "REPRO_DRYRUN_XLA_FLAGS", "--xla_force_host_platform_device_count=512"
    )


def main() -> None:
    force_host_devices()
    ap = argparse.ArgumentParser(description=_DOC)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--approx", choices=["exact", "inject", "model"], default="inject")
    ap.add_argument("--backend", default="analog",
                    help="approximate backend for inject/model cells")
    ap.add_argument("--fused", action="store_true",
                    help="emulated DECODE cells: fused epilogue kernels + "
                         "flash decode attention (the serving hot path)")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-probes", action="store_true",
                    help="skip L1/L2 probe compiles (faster, raw cost only)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    archs = list_archs() if (args.all or args.arch is None) else [args.arch]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    results = []
    existing: Dict[tuple, dict] = {}
    if args.out and os.path.exists(args.out):
        with open(args.out) as f:
            for r in json.load(f):
                existing[(r["arch"], r["shape"], r["mesh"], r["approx"])] = r
    for arch in archs:
        cfg = get_config(arch)
        for shape in shapes_for(cfg):
            if args.shape and shape.name != args.shape:
                continue
            for mp in meshes:
                # multi-pod pass proves the pod axis shards; probes (roofline
                # accounting) run single-pod only per the assignment
                res = run_cell(
                    arch, shape, mp, args.approx,
                    probes=not args.no_probes and not mp,
                    backend=args.backend, fused=args.fused,
                )
                d = dataclasses.asdict(res)
                existing[(d["arch"], d["shape"], d["mesh"], d["approx"])] = d
                results.append(d)
                if args.out:
                    with open(args.out + ".tmp", "w") as f:
                        json.dump(list(existing.values()), f, indent=1)
                    os.replace(args.out + ".tmp", args.out)
    n_ok = sum(r["ok"] for r in results)
    print(f"[dryrun] {n_ok}/{len(results)} cells OK")
    if n_ok < len(results):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
