"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell on 512 placeholder host devices, and extract the roofline
inputs (HLO FLOPs, bytes, per-collective traffic, memory analysis).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen1.5-32b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both --out dryrun.json

The 512-device XLA flag is added to ``XLA_FLAGS`` only when this module
runs as a script, before jax is imported here: a module that imports it
(tests, benches, the LM trainer) keeps the real device count and any
flags set from outside.
"""

import os

if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = " ".join(filter(None, [
        os.environ.get("XLA_FLAGS", ""),
        "--xla_force_host_platform_device_count=512",
    ]))

import argparse
import dataclasses
import json
import re
import time
import traceback
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, get_config
from repro.configs.shapes import HDP_CELLS, SHAPES, SMOKE_SHAPES, cell_applicable
from repro.launch import mesh as MESH
from repro.models import lm as LM
from repro.train.optimizer import AdamWConfig, adamw_init
from repro.train.trainer import TrainState, make_train_step

# ---------------------------------------------------------------------------
# HLO collective-traffic parser
# ---------------------------------------------------------------------------

_COLL = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
         "collective-permute")
_COLL_RE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\("
)
_SHAPE_RE = re.compile(
    r"\b(f64|f32|bf16|f16|s64|u64|s32|u32|s16|u16|s8|u8|pred)\[([0-9,]*)\]"
)
_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
}


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Sum per-device RESULT bytes of every collective op.

    The optimized-HLO dialect prints only the result shape inline
    (operands are bare %refs), so the convention here is "bytes the op
    materializes on each device": equal to operand bytes for all-reduce /
    all-to-all / collective-permute, the post-gather size for all-gather,
    and the post-scatter size for reduce-scatter. EXPERIMENTS.md section
    Roofline uses the same convention when converting to link-seconds.
    """
    totals: dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if m is None:
            continue
        if "-done(" in line or " async-" in line:
            continue  # start op carries the shape; done would double count
        lhs = line[: m.start()]
        if "=" not in lhs:
            continue
        op = m.group(1)
        nbytes = 0
        for dt, dims in _SHAPE_RE.findall(lhs):
            n = 1
            if dims:
                for d in dims.split(","):
                    n *= int(d)
            nbytes += n * _DTYPE_BYTES[dt]
        if nbytes:
            totals[op] = totals.get(op, 0) + nbytes
    return totals


def _memory_analysis(compiled) -> dict:
    out = {}
    try:
        ma = compiled.memory_analysis()
        for f in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes"):
            v = getattr(ma, f, None)
            if v is not None:
                out[f] = int(v)
    except Exception as e:  # pragma: no cover
        out["error"] = str(e)
    return out


def _cost_analysis(compiled) -> dict:
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        return {k: float(v) for k, v in ca.items()
                if isinstance(v, (int, float)) and (
                    "flops" in k or "bytes" in k or "utilization" not in k)}
    except Exception as e:  # pragma: no cover
        return {"error": str(e)}


# ---------------------------------------------------------------------------
# model-FLOPs estimates (roofline "useful compute" numerator)
# ---------------------------------------------------------------------------

def param_counts(cfg) -> dict:
    """Analytic parameter counts (total, active-per-token)."""
    d, l = cfg.d_model, cfg.num_layers
    emb = cfg.vocab_size * d
    attn = 0
    if cfg.attn_active:
        attn = d * cfg.head_dim * (cfg.num_heads * 2 + cfg.num_kv_heads * 2)
    mlp_tot = mlp_act = 0
    if cfg.block_type == "moe":
        gated = 3 if cfg.mlp_type in ("swiglu", "geglu") else 2
        per_e = gated * d * cfg.expert_d_ff
        mlp_tot = cfg.num_experts * per_e + cfg.shared_experts * per_e
        mlp_act = cfg.top_k * per_e + cfg.shared_experts * per_e
        mlp_tot += d * cfg.num_experts
    elif cfg.d_ff:
        gated = 3 if cfg.mlp_type in ("swiglu", "geglu") else 2
        mlp_tot = mlp_act = gated * d * cfg.d_ff
    ssm = 0
    if cfg.ssm_active:
        d_inner = cfg.ssm_expand * d
        heads = d_inner // cfg.ssm_head_dim
        ssm = d * (2 * d_inner + 2 * cfg.ssm_state + heads) + d_inner * d
    if mlp_act == 0:
        mlp_act = mlp_tot
    total = emb + l * (attn + mlp_tot + ssm)
    active = emb + l * (attn + mlp_act + ssm)
    return {"total": int(total), "active": int(active)}


def model_flops(cfg, cell) -> float:
    """6*N_active*D tokens for train; 2*N_active*tokens for inference."""
    pc = param_counts(cfg)
    tokens = cell.global_batch * (cell.seq_len if cell.kind != "decode" else 1)
    mult = 6.0 if cell.kind == "train" else 2.0
    return mult * pc["active"] * tokens


# ---------------------------------------------------------------------------
# abstract inputs (ShapeDtypeStruct stand-ins — no allocation)
# ---------------------------------------------------------------------------

def sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def input_specs(cfg, cell) -> dict:
    """Abstract model inputs for one cell (the task-mandated entry point)."""
    b, s = cell.global_batch, cell.seq_len
    if cell.kind in ("train", "prefill"):
        s_tok = s - cfg.prefix_len
        spec = {
            "tokens": sds((b, s_tok), jnp.int32),
        }
        if cell.kind == "train":
            spec["targets"] = sds((b, s_tok), jnp.int32)
            spec["mask"] = sds((b, s_tok), jnp.bool_)
        if cfg.prefix_len:
            spec["embeds"] = sds((b, cfg.prefix_len, cfg.d_model), cfg.cdtype)
        return spec
    # decode: one token against a cache of length s
    return {"token": sds((b,), jnp.int32), "fill": sds((), jnp.int32)}


def abstract_train_state(cfg):
    box = {}

    def f():
        params, axes = LM.init_lm(jax.random.key(0), cfg)
        box["axes"] = axes
        mu, nu = adamw_init(params)
        return TrainState(params, mu, nu, jnp.zeros((), jnp.int32))

    shapes = jax.eval_shape(f)
    return shapes, box["axes"]


def abstract_params(cfg):
    box = {}

    def f():
        params, axes = LM.init_lm(jax.random.key(0), cfg)
        box["axes"] = axes
        return params

    shapes = jax.eval_shape(f)
    return shapes, box["axes"]


# ---------------------------------------------------------------------------
# per-cell lowering
# ---------------------------------------------------------------------------

def _finish(record, lowered, t_lower):
    t0 = time.time()
    compiled = lowered.compile()
    record["compile_s"] = round(time.time() - t0, 2)
    record["lower_s"] = round(t_lower, 2)
    record["memory"] = _memory_analysis(compiled)
    record["cost"] = _cost_analysis(compiled)
    record["collectives"] = collective_bytes(compiled.as_text())
    record["status"] = "ok"
    return record


def _lower_lm(cfg, cell, mesh, rule_overrides=None):
    """Build the lowered computation for one (cfg, cell) on a mesh."""
    rules_t = MESH.train_rules(mesh)
    rules_s = MESH.serve_rules(mesh)
    if rule_overrides:
        rules_t.update(rule_overrides)
        rules_s.update(rule_overrides)
    spec = input_specs(cfg, cell)
    with mesh:
        if cell.kind == "train":
            state_shapes, axes = abstract_train_state(cfg)
            psh = MESH.shardings_for_tree(
                state_shapes.params, axes, rules_t, mesh
            )
            state_sh = TrainState(
                psh,
                MESH.shardings_for_tree(state_shapes.mu, axes, rules_t, mesh),
                MESH.shardings_for_tree(state_shapes.nu, axes, rules_t, mesh),
                NamedSharding(mesh, P()),
            )
            batch_sh = MESH.batch_shardings(mesh, spec, rules_t)
            step = make_train_step(cfg, AdamWConfig())
            met_sh = {k: NamedSharding(mesh, P())
                      for k in ("loss", "grad_norm", "skipped")}
            lowered = jax.jit(
                step,
                in_shardings=(state_sh, batch_sh),
                out_shardings=(state_sh, met_sh),
                donate_argnums=(0,),
            ).lower(state_shapes, spec)
        elif cell.kind == "prefill":
            params_shapes, axes = abstract_params(cfg)
            psh = MESH.shardings_for_tree(params_shapes, axes, rules_s, mesh)
            cache_len = min(cell.seq_len, cfg.window) if cfg.window else cell.seq_len

            def prefill_fn(params, tokens, embeds=None):
                return LM.prefill(params, cfg, tokens, cache_len, embeds)

            cache_shapes = jax.eval_shape(
                lambda: LM.init_cache(cfg, cell.global_batch, cache_len)
            )
            cache_sh = MESH.kv_cache_shardings(mesh, cfg, cache_shapes, rules_s)
            logits_sh = NamedSharding(
                mesh, MESH.spec_for(
                    (cell.global_batch, cfg.vocab_size), ("batch", "vocab"),
                    rules_s, mesh,
                )
            )
            batch_sh = MESH.batch_shardings(mesh, spec, rules_s)
            args = (params_shapes, spec["tokens"])
            in_sh = (psh, batch_sh["tokens"])
            if cfg.prefix_len:
                args += (spec["embeds"],)
                in_sh += (batch_sh["embeds"],)
            lowered = jax.jit(
                prefill_fn, in_shardings=in_sh,
                out_shardings=(logits_sh, cache_sh),
            ).lower(*args)
        else:  # decode
            params_shapes, axes = abstract_params(cfg)
            psh = MESH.shardings_for_tree(params_shapes, axes, rules_s, mesh)
            cache_len = min(cell.seq_len, cfg.window) if cfg.window else cell.seq_len
            cache_shapes = jax.eval_shape(
                lambda: LM.init_cache(cfg, cell.global_batch, cache_len)
            )
            cache_sh = MESH.kv_cache_shardings(mesh, cfg, cache_shapes, rules_s)
            logits_sh = NamedSharding(
                mesh, MESH.spec_for(
                    (cell.global_batch, cfg.vocab_size), ("batch", "vocab"),
                    rules_s, mesh,
                )
            )
            tok_sh = MESH.batch_shardings(mesh, {"token": spec["token"]},
                                          rules_s)["token"]

            def decode_fn(params, token, cache, fill):
                return LM.decode_step(params, cfg, token, cache, fill)

            lowered = jax.jit(
                decode_fn,
                in_shardings=(psh, tok_sh, cache_sh, NamedSharding(mesh, P())),
                out_shardings=(logits_sh, cache_sh),
                donate_argnums=(2,),
            ).lower(params_shapes, spec["token"], cache_shapes, spec["fill"])
    return lowered


def _extrapolate(v1: dict, v2: dict, n: int) -> dict:
    """total = fixed + n*body from measurements at n=1, n=2."""
    out = {}
    for k in set(v1) | set(v2):
        a, b = float(v1.get(k, 0.0)), float(v2.get(k, 0.0))
        body = max(b - a, 0.0)
        out[k] = a + (n - 1) * body
    return out


def _lm_cost_probe(cfg, cell, mesh, rule_overrides=None) -> dict:
    """Corrected per-device cost: XLA cost_analysis counts while-loop
    bodies ONCE, so scanned stacks undercount by ~num_layers. Lower the
    stack UNROLLED at L=1 and L=2 (cheap), then extrapolate
    total = fixed + L*layer for flops, bytes and collective traffic.
    Exact for homogeneous stacks (all assigned archs). The probe also
    disables loss chunking and query-chunked attention (both lax.map
    loops) so their bodies are fully counted."""
    import repro.kernels.flash_attention.ops as fops

    old_thr = fops.CHUNKED_THRESHOLD
    fops.CHUNKED_THRESHOLD = 1 << 60
    try:
        vals = {}
        for layers in (1, 2):
            cfg_p = dataclasses.replace(
                cfg, num_layers=layers, scan_layers=False,
                loss_chunk=1 << 30,
            )
            compiled = _lower_lm(cfg_p, cell, mesh, rule_overrides).compile()
            cost = _cost_analysis(compiled)
            coll = collective_bytes(compiled.as_text())
            vals[layers] = {
                "flops": cost.get("flops", 0.0),
                "bytes accessed": cost.get("bytes accessed", 0.0),
                **{f"coll/{k}": v for k, v in coll.items()},
            }
        out = _extrapolate(vals[1], vals[2], cfg.num_layers)
        out["probe"] = "unrolled L1/L2 extrapolation"
        return out
    finally:
        fops.CHUNKED_THRESHOLD = old_thr


def lm_cell(arch: str, shape_name: str, multi_pod: bool, smoke: bool = False,
            probe: bool = True, rule_overrides=None, act_mode=None):
    """act_mode: None = per-config; "none" strips sequence parallelism;
    "seq" shards the residual carry (batch, model@seq, -); "embed" shards
    it (batch, -, model@embed). rule_overrides patches the logical-axis
    rules (e.g. {"batch": ("data", "model")} = DP-only layout)."""
    cfg = get_config(arch, smoke=smoke)
    cell = (SMOKE_SHAPES if smoke else SHAPES)[shape_name]
    record = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "model_flops": model_flops(cfg, cell),
        "params": param_counts(cfg),
    }
    ok, reason = cell_applicable(cfg, cell)
    if not ok:
        record["status"] = "skipped"
        record["reason"] = reason
        return record

    mesh = MESH.make_production_mesh(multi_pod=multi_pod)
    ba = MESH.batch_axes(mesh)
    ba = ba if len(ba) > 1 else ba[0]
    if act_mode is None:
        act_mode = "seq" if cfg.act_shard_seq else "none"
    if act_mode == "seq":
        cfg = dataclasses.replace(cfg, act_spec=(ba, "model", None))
    elif act_mode == "embed":
        cfg = dataclasses.replace(cfg, act_spec=(ba, None, "model"))
    elif act_mode == "batch":
        # anchor only the batch dim of the residual carry: prevents the
        # partitioner from drifting to replicated/partial-sum strategies
        # between layers (observed on low-head-count archs).
        cfg = dataclasses.replace(cfg, act_spec=(ba, None, None))
    else:
        cfg = dataclasses.replace(cfg, act_spec=None)
    t0 = time.time()
    lowered = _lower_lm(cfg, cell, mesh, rule_overrides)
    record = _finish(record, lowered, time.time() - t0)
    if probe:
        try:
            record["cost_corrected"] = _lm_cost_probe(
                cfg, cell, mesh, rule_overrides
            )
        except Exception as e:
            record["cost_corrected"] = {"error": f"{type(e).__name__}: {e}"}
    return record


def hdp_cell(cell_name: str, multi_pod: bool, z_impl: str = "sparse",
             gather_tables: bool = True, smoke: bool = False,
             phi_dtype: str = "f32", compact_tables: bool = False,
             bucket: int = 64):
    from repro.core import hdp as H
    from repro.core.sharded import ShardedHDP

    cell = HDP_CELLS[cell_name]
    if smoke:
        cell = cell._replace(V=1024, D=1024, max_len=64, K=32)
    record = {
        "arch": cell_name, "shape": "gibbs_iteration",
        "mesh": "2x16x16" if multi_pod else "16x16",
        "z_impl": z_impl, "gather_tables": gather_tables,
        "phi_dtype": phi_dtype, "compact_tables": compact_tables,
        # all HDP collectives sit outside the z while-loop, so the raw
        # (main-lowering) counts are exact — roofline prefers them.
        "collectives_exact": True,
        # z-step work estimate: tokens * (alias O(1) + bucket scan)
        "model_flops": float(cell.D) * cell.max_len * 3 * 64,
    }
    mesh = MESH.make_production_mesh(multi_pod=multi_pod)
    cfg = H.HDPConfig(
        K=cell.K, V=cell.V, bucket=bucket, z_impl=z_impl,
        hist_cap=min(cell.max_len, 256),
    )
    sh = ShardedHDP(
        mesh, cfg, gather_tables=gather_tables,
        phi_dtype=jnp.bfloat16 if phi_dtype == "bf16" else jnp.float32,
        compact_tables=compact_tables,
    )
    key_sds = jax.eval_shape(lambda: jax.random.key(0))
    state = H.HDPState(
        z=sds((cell.D, cell.max_len), jnp.int32),
        n=sds((cell.K, cell.V), jnp.int32),
        phi=sds((cell.K, cell.V), jnp.float32),
        varphi=sds((cell.K, cell.V), jnp.int32),
        psi=sds((cell.K,), jnp.float32),
        l=sds((cell.K,), jnp.int32),
        key=key_sds,
        it=sds((), jnp.int32),
    )
    tokens = sds((cell.D, cell.max_len), jnp.int32)
    mask = sds((cell.D, cell.max_len), jnp.bool_)
    t0 = time.time()
    with mesh:
        lowered = jax.jit(
            sh.iteration_fn(),
            in_shardings=(sh.state_shardings(), *sh.corpus_shardings()),
            out_shardings=sh.state_shardings(),
            donate_argnums=(0,),
        ).lower(state, tokens, mask)
    record = _finish(record, lowered, time.time() - t0)
    try:
        record["cost_corrected"] = _hdp_cost_probe(
            cell, mesh, z_impl, gather_tables
        )
    except Exception as e:
        record["cost_corrected"] = {"error": f"{type(e).__name__}: {e}"}
    return record


def _hdp_cost_probe(cell, mesh, z_impl, gather_tables) -> dict:
    """Same while-body correction as _lm_cost_probe, along the document
    length: unrolled in-document sweeps at max_len 1 and 2, extrapolated
    to the real packed length. (The K-step alias-build scan body stays
    counted once; its true cost ~25*K*V_shard flops is negligible next to
    the z-step and is noted in EXPERIMENTS.md.)"""
    from repro.core import hdp as H
    from repro.core.sharded import ShardedHDP

    if z_impl == "pallas":
        z_impl = "sparse"  # interpret-mode kernel: probe the jnp twin
    vals = {}
    for ln in (1, 2):
        cfg = H.HDPConfig(K=cell.K, V=cell.V, bucket=64, z_impl=z_impl,
                          hist_cap=min(cell.max_len, 256), unroll_z=True)
        sh = ShardedHDP(mesh, cfg, gather_tables=gather_tables)
        key_sds = jax.eval_shape(lambda: jax.random.key(0))
        state = H.HDPState(
            z=sds((cell.D, ln), jnp.int32),
            n=sds((cell.K, cell.V), jnp.int32),
            phi=sds((cell.K, cell.V), jnp.float32),
            varphi=sds((cell.K, cell.V), jnp.int32),
            psi=sds((cell.K,), jnp.float32),
            l=sds((cell.K,), jnp.int32),
            key=key_sds, it=sds((), jnp.int32),
        )
        tokens = sds((cell.D, ln), jnp.int32)
        mask = sds((cell.D, ln), jnp.bool_)
        with mesh:
            compiled = jax.jit(
                sh.iteration_fn(),
                in_shardings=(sh.state_shardings(), *sh.corpus_shardings()),
                out_shardings=sh.state_shardings(),
            ).lower(state, tokens, mask).compile()
        cost = _cost_analysis(compiled)
        coll = collective_bytes(compiled.as_text())
        vals[ln] = {
            "flops": cost.get("flops", 0.0),
            "bytes accessed": cost.get("bytes accessed", 0.0),
            **{f"coll/{k}": v for k, v in coll.items()},
        }
    out = _extrapolate(vals[1], vals[2], cell.max_len)
    out["probe"] = "unrolled maxlen1/2 extrapolation"
    return out


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def run_cells(archs, shapes, meshes, out_path: Optional[str], smoke=False,
              hdp=(), z_impl="sparse"):
    results = []
    for multi_pod in meshes:
        for name in hdp:
            t0 = time.time()
            try:
                rec = hdp_cell(name, multi_pod, z_impl=z_impl, smoke=smoke)
            except Exception as e:
                rec = {"arch": name, "shape": "gibbs_iteration",
                       "mesh": "2x16x16" if multi_pod else "16x16",
                       "status": "error", "error": f"{type(e).__name__}: {e}",
                       "trace": traceback.format_exc()[-2000:]}
            rec["wall_s"] = round(time.time() - t0, 1)
            results.append(rec)
            _report(rec)
        for arch in archs:
            for shape in shapes:
                t0 = time.time()
                try:
                    rec = lm_cell(arch, shape, multi_pod, smoke=smoke)
                except Exception as e:
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "2x16x16" if multi_pod else "16x16",
                           "status": "error",
                           "error": f"{type(e).__name__}: {e}",
                           "trace": traceback.format_exc()[-2000:]}
                rec["wall_s"] = round(time.time() - t0, 1)
                results.append(rec)
                _report(rec)
                if out_path:
                    with open(out_path, "w") as f:
                        json.dump(results, f, indent=1)
    if out_path:
        with open(out_path, "w") as f:
            json.dump(results, f, indent=1)
    return results


def _report(rec):
    s = rec.get("status")
    extra = ""
    if s == "ok":
        fl = rec.get("cost", {}).get("flops", 0)
        cb = sum(rec.get("collectives", {}).values())
        extra = f"flops={fl:.3g} coll={cb/1e6:.1f}MB"
    elif s == "error":
        extra = rec.get("error", "")[:160]
    elif s == "skipped":
        extra = rec.get("reason", "")[:80]
    print(f"[{rec['mesh']}] {rec['arch']} x {rec['shape']}: {s} "
          f"({rec.get('wall_s', '?')}s) {extra}", flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--hdp", default=None,
                    help="comma-separated HDP cells (or 'all')")
    ap.add_argument("--z-impl", default="sparse")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced configs (CI sanity)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    if args.all:
        archs, shapes = ARCHS, list(SHAPES)
        hdp = list(HDP_CELLS)
    else:
        archs = [args.arch] if args.arch and args.arch in set(ARCHS) else []
        shapes = [args.shape] if args.shape else list(SHAPES)
        hdp = []
        if args.hdp:
            hdp = list(HDP_CELLS) if args.hdp == "all" else args.hdp.split(",")
        if args.arch and args.arch in HDP_CELLS:
            hdp = [args.arch]
    run_cells(archs, shapes, meshes, args.out, smoke=args.smoke, hdp=hdp,
              z_impl=args.z_impl)


if __name__ == "__main__":
    main()
