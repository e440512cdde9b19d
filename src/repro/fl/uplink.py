"""Fleet-batched compressed uplinks: the ``REPRO_UPLINK`` hot path.

EchoPFL's bandwidth asymmetry (thin ~10 MB/s uplink vs fat ~100 MB/s
downlink) makes the *uplink* the communication bottleneck, and the paper's
comm-cost claim (~37% total-bytes reduction) rests on compressing it. The
codecs in :mod:`repro.optim.compression` supply the arithmetic; this module
wires them into the simulator's upload path as batched launches:

* Every client owns an **anchor row** in a dedicated
  :class:`~repro.core.plane.ParameterPlane`: the last model value both
  sides agree on. It is seeded with the initial broadcast, advanced to the
  *reconstruction* of every upload (the server applies exactly the
  decompressed delta, so both ends advance in lockstep), and refreshed to
  every downlinked model the client installs (:meth:`UplinkCodec.install`
  — the server knows what it sent, so this costs zero wire bytes and keeps
  the delta measured against the client's actual training base).
* An upload compresses ``delta = trained - anchor``. Under ``topk`` the
  delta passes through error-feedback top-k, whose residual lives in a
  second per-client plane row (restored by ``load_state`` alongside the
  anchor); under ``int8`` it quantizes with per-chunk scales. Either way
  the reconstruction ``anchor + decompress(payload)`` is handed onward, so
  the server's ingest (``ingest_chain`` / ``handle_uploads``) and the
  broadcast predictor's want-sync statistics see exactly what crossed the
  compressed wire — no ingest-side changes, no second decompression pass.
* A cohort of B concurrent uploads (a coalesced window, a sync round) is
  ONE fused launch: gather the anchor/residual banks in-jit, compress all
  rows, write the updated state back through the plane's staged (donated)
  scatter. B = 1 runs the same launch, so the per-event loop and a
  degenerate coalescing window stay bitwise-identical.
* The payload's exact wire size — int32 indices + f32 values, or int8
  codes + f32 per-chunk scales — depends only on static config, so
  :meth:`UplinkCodec.nbytes` bills every compressed uplink without a
  device sync (``compression.wire_bytes`` == ``payload_bytes`` of the
  emitted payload; the regression tests pin the equality).

Knobs (read at simulator construction; constructor args win):

* ``REPRO_UPLINK`` — ``none`` (default; the uncompressed path, bitwise the
  pre-codec trajectories) | ``topk`` | ``int8``.
* ``REPRO_UPLINK_K`` — top-k budget: a fraction of the flat dim in (0, 1)
  (default ``0.1``) or an absolute count ``>= 1``.
* ``REPRO_UPLINK_CHUNK`` — int8 scale-chunk length (default ``512``),
  clamped to the flat dim.
"""
from __future__ import annotations

import dataclasses
import functools
import os
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.pytrees import flatten_spec
from repro.common.tracing import fetch, span
from repro.core.plane import ParameterPlane
from repro.optim.compression import (
    Int8Payload,
    TopKPayload,
    ef_topk_batch,
    int8_compress_batch,
    int8_decompress_batch,
    payload_bytes,
    wire_bytes,
)

PyTree = Any

UPLINK_MODES = ("none", "topk", "int8")


@dataclasses.dataclass(frozen=True)
class UplinkConfig:
    """Static uplink-compression config (mode + codec geometry)."""

    mode: str = "none"
    k: float = 0.1  # topk budget: fraction of dim in (0, 1) or absolute count >= 1
    chunk: int = 512  # int8 per-chunk scale granularity

    def __post_init__(self):
        if self.mode not in UPLINK_MODES:
            raise ValueError(
                f"REPRO_UPLINK mode must be one of {UPLINK_MODES}, got {self.mode!r}"
            )
        if self.k <= 0:
            raise ValueError(f"REPRO_UPLINK_K must be positive, got {self.k}")
        if self.chunk < 1:
            raise ValueError(f"REPRO_UPLINK_CHUNK must be >= 1, got {self.chunk}")

    def resolve_k(self, dim: int) -> int:
        """Concrete per-row keep count for a flat dim: fractions round, both
        forms clamp into [1, dim]."""
        k = self.k * dim if self.k < 1 else self.k
        return max(1, min(dim, int(round(k))))

    def resolve_chunk(self, dim: int) -> int:
        return max(1, min(dim, int(self.chunk)))


def default_uplink() -> str:
    """``REPRO_UPLINK`` knob: ``none`` (uncompressed, the parity default) |
    ``topk`` (EF-top-k deltas) | ``int8`` (per-chunk quantized deltas)."""
    return os.environ.get("REPRO_UPLINK", "none").strip().lower() or "none"


def uplink_config_from_env() -> UplinkConfig:
    return UplinkConfig(
        mode=default_uplink(),
        k=float(os.environ.get("REPRO_UPLINK_K", "0.1")),
        chunk=int(os.environ.get("REPRO_UPLINK_CHUNK", "512")),
    )


def resolve_uplink(spec: Any) -> UplinkConfig:
    """Coerce a constructor argument (None -> env, a mode string, or a full
    :class:`UplinkConfig`) into a validated config."""
    if spec is None:
        return uplink_config_from_env()
    if isinstance(spec, UplinkConfig):
        return spec
    env = uplink_config_from_env()
    return UplinkConfig(mode=str(spec).strip().lower() or "none", k=env.k, chunk=env.chunk)


def _pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


@functools.partial(jax.jit, static_argnames=("k",))
def _encode_topk(bank_a, bank_r, sel, mat, *, k: int):
    # anchor/residual rows gather from the plane banks INSIDE the launch
    # (cached incrementally-patched views — same economics as the fleet's
    # model-row bank), fused with the EF-top-k compress + reconstruct
    A = bank_a[sel]
    _idx, _vals, sent, new_r = ef_topk_batch(mat - A, bank_r[sel], k)
    return A + sent, new_r


@functools.partial(jax.jit, static_argnames=("chunk",))
def _encode_int8(bank_a, sel, mat, *, chunk: int):
    A = bank_a[sel]
    q, scales = int8_compress_batch(mat - A, chunk)
    return A + int8_decompress_batch(q, scales, chunk)


class UplinkCodec:
    """Per-client uplink compression state + one-launch cohort encoding.

    Owns a dedicated :class:`ParameterPlane` whose rows are each client's
    anchor (and, under ``topk``, EF residual). :meth:`encode_vecs` is the
    single entry point: compress a ``(B, dim)`` cohort of trained models
    against their anchors, advance the state rows, and hand back the
    reconstructed uploads the server ingests — one fused launch regardless
    of B. The strategy adopting the codec (``attach_uplink_codec``) carries
    its rows through ``state_dict``/``load_state`` checkpoints."""

    def __init__(self, template: PyTree, client_ids: Sequence[Any], config: UplinkConfig):
        if config.mode == "none":
            raise ValueError("UplinkCodec requires mode topk|int8 (none means no codec)")
        self.config = config
        self.mode = config.mode
        self.spec = flatten_spec(template)
        self.dim = self.spec.dim
        self.k = config.resolve_k(self.dim)
        self.chunk = config.resolve_chunk(self.dim)
        self.ids = list(client_ids)
        self.index = {cid: i for i, cid in enumerate(self.ids)}
        K = len(self.ids)
        self.plane = ParameterPlane(template, capacity=(2 * K if self.mode == "topk" else K))
        self._anchor_row = self.plane.alloc_many(K)
        self._resid_row = self.plane.alloc_many(K) if self.mode == "topk" else None
        self._seeded = [False] * K
        self._released = [False] * K  # evicted clients: rows returned to the plane
        self._install_memo: tuple[Any, Any] = (None, None)  # (params obj, flat vec)
        self._zero_vec = jnp.zeros((self.dim,), self.plane.dtype)
        self.launches = 0  # fused encode launches issued (bench introspection)
        # exact wire size of ONE compressed upload — static config only, and
        # pinned equal to payload_bytes() of the emitted payload shape
        self.nbytes = wire_bytes(self.mode, self.dim, k=self.k, chunk=self.chunk)
        assert self.nbytes == payload_bytes(self.payload_template())

    def payload_template(self):
        """A zero payload with the exact shapes/dtypes every upload ships —
        the byte-accounting tests feed this to ``payload_bytes``."""
        if self.mode == "topk":
            return TopKPayload(
                indices=np.zeros(self.k, np.int32),
                values=np.zeros(self.k, np.float32),
                length=self.dim,
            )
        n_chunks = -(-self.dim // self.chunk)
        return Int8Payload(
            q=np.zeros(self.dim, np.int8),
            scales=np.zeros(n_chunks, np.float32),
            chunk=self.chunk,
        )

    # -------------------------------------------------------------- seeding
    def seed(self, models: dict[Any, PyTree]) -> None:
        """Install initial anchors from a broadcast both sides saw (the run
        start's ``initial_models``). Clients whose anchors already exist —
        restored from a checkpoint, or seeded by an earlier run — are left
        untouched, so a restart never clobbers live codec state."""
        by_obj: dict[int, jax.Array] = {}  # a broadcast fans one object: flatten once
        rows, vecs = [], []
        for cid, params in models.items():
            i = self.index.get(cid)
            if i is None or self._seeded[i] or self._released[i]:
                continue
            key = id(params)
            vec = by_obj.get(key)
            if vec is None:
                vec = by_obj[key] = self.spec.flatten(params)
            rows.append(self._anchor_row[i])
            vecs.append(vec)
            self._seeded[i] = True
        if rows:
            self.plane.write_rows(rows, jnp.stack(vecs))

    def install(self, cid, params: PyTree) -> None:
        """Advance a client's anchor to a just-downlinked model — a value
        both sides agree on (the server sent it, the client installed it),
        at zero wire cost. Without this the anchor would trail the last
        upload's reconstruction while the client trains from fresher
        downlinks, and the growing ``trained - anchor`` delta would swamp a
        top-k budget (EF residual blow-up on unicast-heavy strategies).
        The EF residual is DROPPED with the old anchor: it carried delta
        mass measured against a base the downlink just superseded, and in
        model-delta space (clients re-train toward the same displacement
        every round) re-adding it double-counts — the corrected vector
        grows linearly and the reconstruction overshoots until divergence.
        Error feedback therefore spans exactly the uploads *between* two
        downlinks. A broadcast fans ONE object at many clients, so
        consecutive installs of the same pytree share a single flatten."""
        i = self.index.get(cid)
        if i is None or self._released[i]:
            return
        obj, vec = self._install_memo
        if obj is not params:
            vec = self.spec.flatten(params)
            self._install_memo = (params, vec)
        self.plane.write(self._anchor_row[i], vec)
        if self._resid_row is not None:
            self.plane.write(self._resid_row[i], self._zero_vec)
        self._seeded[i] = True

    def release_client(self, cid) -> None:
        """Free a dead/evicted client's codec rows (anchor + EF residual)
        back to the plane. ``evict_clients`` calls this alongside the
        server-side reclamation — without it every death leaked
        ``1 + (mode == topk)`` rows of codec state for the rest of the
        run. Idempotent; released clients drop out of seeding, installs,
        checkpoints, and the encode bank gather."""
        i = self.index.get(cid)
        if i is None or self._released[i]:
            return
        self.plane.free(self._anchor_row[i])
        if self._resid_row is not None:
            self.plane.free(self._resid_row[i])
        self._released[i] = True
        self._seeded[i] = False

    # ------------------------------------------------------------- encoding
    def _bank_rows(self, rows: Sequence[int]) -> tuple[int, ...]:
        """Bank-gather row tuple with released clients' entries redirected
        to a live stand-in row: a released client never uploads again, so
        its entry is never selected — the stand-in only keeps the gather
        off freed (re-allocatable) plane rows while the bank keeps its
        stable shape and cache key."""
        if not any(self._released):
            return tuple(rows)
        stand_in = next(
            (r for r, dead in zip(rows, self._released) if not dead), rows[0]
        )
        return tuple(
            stand_in if dead else r for r, dead in zip(rows, self._released)
        )
    def encode_vecs(self, cids: Sequence[Any], mat) -> np.ndarray:
        """ONE fused launch: compress ``mat[i]`` (client ``cids[i]``'s
        trained flat model) against its anchor, advance anchor/residual
        rows, and return the ``(B, dim)`` reconstructed uploads as a frozen
        host matrix. ``cids`` must be distinct (one in-flight round per
        client — the event loop's invariant). Cohorts pad to the next power
        of two (padding rows recompute row 0 and are dropped), so the jit
        cache stays O(log fleet)."""
        idx = [self.index[c] for c in cids]
        for c, i in zip(cids, idx):
            if self._released[i]:
                raise ValueError(f"client {c}'s uplink codec rows were released")
            if not self._seeded[i]:
                raise ValueError(f"client {c} has no uplink anchor seeded")
        with span("codec", rows=len(idx)):
            B = len(idx)
            P = _pow2(B)
            sel = np.asarray(idx + [idx[0]] * (P - B), np.int32)
            mat = jnp.asarray(mat, self.plane.dtype)
            if P != B:
                mat = jnp.concatenate([mat, jnp.broadcast_to(mat[:1], (P - B, mat.shape[1]))])
            bank_a = self.plane.rows(self._bank_rows(self._anchor_row))
            self.launches += 1
            if self.mode == "topk":
                bank_r = self.plane.rows(self._bank_rows(self._resid_row))
                rec, new_r = _encode_topk(bank_a, bank_r, sel, mat, k=self.k)
                rec = rec[:B]
                rows = [self._resid_row[i] for i in idx] + [self._anchor_row[i] for i in idx]
                self.plane.write_rows(rows, jnp.concatenate([new_r[:B], rec], axis=0))
            else:
                rec = _encode_int8(bank_a, sel, mat, chunk=self.chunk)[:B]
                self.plane.write_rows([self._anchor_row[i] for i in idx], rec)
            rec_np = np.asarray(fetch(rec, "codec"))
            # the reconstructed pytrees hand out views over this matrix: freeze
            # it so an (unsupported) in-place mutation raises, like fleet outputs
            rec_np.flags.writeable = False
            return rec_np

    def encode_rows(self, cids: Sequence[Any], mat) -> tuple[list[PyTree], int]:
        """Cohort form: reconstructed per-client pytrees (numpy views over
        one matrix) + the per-upload wire bytes."""
        rec = self.encode_vecs(cids, mat)
        return [self.spec.unflatten_np(v) for v in rec], self.nbytes

    def encode(self, cid, params: PyTree) -> tuple[PyTree, int]:
        """Single-upload form (the per-event loop): same launch at B = 1."""
        vec = params if isinstance(params, jax.Array) and params.ndim == 1 else self.spec.flatten(params)
        rec = self.encode_vecs([cid], vec[None, :])
        return self.spec.unflatten_np(rec[0]), self.nbytes

    # ------------------------------------------------ checkpoint/restart
    def state_dict(self) -> tuple[PyTree, dict]:
        """(array_tree, json_meta) of the codec's live rows: per-client
        anchors (+ EF residuals under ``topk``). Without them a restarted
        compressed run would re-anchor at zero and the first post-restart
        upload per client would ship a full-model-sized delta through the
        codec — wrong bytes AND wrong arithmetic."""
        seeded = [cid for cid in self.ids if self._seeded[self.index[cid]]]
        tree: dict[str, Any] = {
            "anchors": {
                str(cid): self.plane.to_pytree(self._anchor_row[self.index[cid]])
                for cid in seeded
            }
        }
        if self.mode == "topk":
            tree["residuals"] = {
                str(cid): self.plane.to_pytree(self._resid_row[self.index[cid]])
                for cid in seeded
            }
        meta = {
            "mode": self.mode,
            "k": self.k,
            "chunk": self.chunk,
            "clients": sorted(str(cid) for cid in seeded),
        }
        return tree, meta

    def load_state(self, tree: PyTree, meta: dict, client_id_type=int) -> None:
        """Restore from :meth:`state_dict` output. Pre-restore rows are
        dropped (re-zeroed) first, exactly like the server's upload rows;
        codec geometry (``k``/``chunk``) follows the CURRENT config — only
        the mode must match, since residuals/anchors are mode-specific."""
        if meta["mode"] != self.mode:
            raise ValueError(
                f"uplink codec mode mismatch: checkpoint is {meta['mode']!r}, "
                f"this run is {self.mode!r}"
            )
        K = len(self.ids)
        live = [i for i in range(K) if not self._released[i]]
        zeros = jnp.zeros((len(live), self.dim), self.plane.dtype)
        self.plane.write_rows([self._anchor_row[i] for i in live], zeros)
        if self._resid_row is not None:
            self.plane.write_rows([self._resid_row[i] for i in live], zeros)
        self._seeded = [False] * K

        def restore(section: dict, row_of: list[int]) -> None:
            rows, vecs = [], []
            for s, p in section.items():
                i = self.index.get(client_id_type(s))
                if i is None or self._released[i]:  # not simulated / evicted
                    continue
                rows.append(row_of[i])
                vecs.append(self.spec.flatten(p))
            if rows:
                self.plane.write_rows(rows, jnp.stack(vecs))

        restore(tree.get("anchors") or {}, self._anchor_row)
        for s in (tree.get("anchors") or {}):
            i = self.index.get(client_id_type(s))
            if i is not None and not self._released[i]:
                self._seeded[i] = True
        if self.mode == "topk":
            restore(tree.get("residuals") or {}, self._resid_row)


def seed_template(meta: dict, params_template: PyTree) -> PyTree:
    """Tree-structure template matching :meth:`UplinkCodec.state_dict` for
    ``meta`` — lets a checkpointer restore the codec section without
    pickling (every row shares the model parameter structure)."""
    tree: dict[str, Any] = {"anchors": {c: params_template for c in meta["clients"]}}
    if meta["mode"] == "topk":
        tree["residuals"] = {c: params_template for c in meta["clients"]}
    return tree
