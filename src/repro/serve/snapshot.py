"""Frozen-model snapshots: the deployable artifact of an HDP run.

A ``ModelSnapshot`` is one posterior sample (Phi, Psi) plus everything
query inference needs, precomputed ONCE:

  phi    (K, V) f32|bf16 : topic-word probabilities (PPU-normalized)
  psi    (K,)   f32      : global topic distribution
  q_a    (V,)   f32      : per-word term-(a) mass sum_k phi[k,v] alpha psi_k
  fpack  (V, 2, W)       : word-sparse [phi values, alias probs]
  ipack  (V, 2, W)       : word-sparse [topic ids, alias donor slots]
  alpha  ()     f32      : document DP concentration used at training

Training rebuilds these tables every Gibbs iteration because Phi moves;
under partial collapsing a *frozen* (Phi, Psi) makes them exact for the
lifetime of the snapshot — the serving-side invariant this module pins
down. Tables are built with ``order="topic"`` so the fold-in sampler
inherits the z-step conformance contract (core/conformance.py): dense,
sparse, and pallas execution of a query are bitwise-identical.

``compact=True`` stores phi/fpack in bf16 and ipack in int16 (valid for
K* <= 32768, enforced at build and load), roughly halving the artifact
and its HBM residency.
"""

from __future__ import annotations

import os
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.kernels.hdp_z import ops as zops
from repro.train import checkpoint as CKPT


class ModelSnapshot(NamedTuple):
    phi: jax.Array     # (K, V)
    psi: jax.Array     # (K,)
    q_a: jax.Array     # (V,)
    fpack: jax.Array   # (V, 2, W)
    ipack: jax.Array   # (V, 2, W)
    alpha: jax.Array   # () f32
    it: jax.Array      # () i32 — source Gibbs iteration (provenance)

    @property
    def K(self) -> int:
        return self.phi.shape[0]

    @property
    def V(self) -> int:
        return self.phi.shape[1]

    @property
    def W(self) -> int:
        return self.fpack.shape[-1]

    @property
    def compact(self) -> bool:
        return self.fpack.dtype == jnp.bfloat16

    def nbytes(self) -> int:
        return sum(a.nbytes for a in self)


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def validate_compact(k: int, where: str):
    """The compact layout's hard precondition: int16 ``ipack`` stores
    topic ids 0..K-1, which silently wrap past 32767 — corrupting every
    draw that touches a high topic — instead of failing. Enforced at
    build AND load time (an artifact may have been produced by other
    code or a future K* growth path)."""
    if k > 2**15:
        raise ValueError(
            f"compact int16 topic ids are only valid for K <= 32768; "
            f"{where} has K={k}. Rebuild without compact=True."
        )


def build_snapshot(
    phi: jax.Array, psi: jax.Array, alpha: float, *,
    w: Optional[int] = None, compact: bool = False, it: int = 0,
) -> ModelSnapshot:
    """Distill (Phi, Psi) into a snapshot.

    ``w`` defaults to the exact table width: the largest per-word topic
    support in Phi, rounded up to a multiple of 128 (one TPU lane row,
    which the compiled hdp_z kernel needs) and capped at K. Passing
    a smaller ``w`` drops each word's smallest-phi topics beyond W —
    a lossy, smaller artifact; the default is exact.
    """
    phi = jnp.asarray(phi, jnp.float32)
    psi = jnp.asarray(psi, jnp.float32)
    k = phi.shape[0]
    if w is None:
        w = max(_round_up(int(zops.max_column_nnz(phi)), 128), 128)
    w = min(w, k)
    if compact:
        validate_compact(k, "build_snapshot(phi)")
    q_a, fpack, ipack = zops.build_word_sparse_tables(
        phi, psi, float(alpha), w, compact=compact, order="topic"
    )
    return ModelSnapshot(
        phi=phi.astype(jnp.bfloat16) if compact else phi,
        psi=psi, q_a=q_a, fpack=fpack, ipack=ipack,
        alpha=jnp.float32(alpha), it=jnp.int32(it),
    )


def snapshot_from_state(state, cfg, *, w: Optional[int] = None,
                        compact: bool = False) -> ModelSnapshot:
    """From a monolithic ``HDPState`` or streaming ``StreamingState``
    (both carry phi/psi/it) + its ``HDPConfig``."""
    return build_snapshot(
        state.phi, state.psi, cfg.alpha, w=w, compact=compact,
        it=int(state.it),
    )


# -- persistence --------------------------------------------------------------
# Snapshots reuse the checkpoint store (atomic commit, bf16 round-trip),
# always at the FIXED step 0: a snapshot dir holds exactly one artifact
# and save() replaces it through checkpoint.py's atomic rename, so a
# crash mid-save can never leave load() picking a stale snapshot by
# step-number accident (source iteration provenance lives in the ``it``
# payload field, not the dir name). Loading is template-free via
# CKPT.restore_flat — shapes/dtypes come from the manifest.

_STEP = 0


def save(path: str, snap: ModelSnapshot) -> str:
    return CKPT.save(path, _STEP, snap._asdict(), keep=0)


def load(path: str) -> ModelSnapshot:
    if not os.path.exists(os.path.join(path, f"step_{_STEP}",
                                       "manifest.json")):
        raise FileNotFoundError(f"no model snapshot at {path!r}")
    flat = CKPT.restore_flat(path, _STEP)
    missing = [f for f in ModelSnapshot._fields if f not in flat]
    if missing:
        raise ValueError(f"{path!r} is not a model snapshot: missing {missing}")
    snap = ModelSnapshot(**{f: flat[f] for f in ModelSnapshot._fields})
    if snap.ipack.dtype == jnp.int16:
        validate_compact(snap.K, f"snapshot at {path!r}")
    return snap
