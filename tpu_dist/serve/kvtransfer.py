"""KV-cache transfer between prefill and decode ranks (disaggregated serving).

The wire half of ``tpu_dist.serve.disagg``: a prefill rank computes one
request's per-layer KV rows (``TransformerLM.prefill_rows``) and ships
them to the decode rank that owns the request as **per-layer contiguous
fragments** over the existing p2p data plane — every fragment rides one
CRC-sealed frame (``transport._send_frame``), so a bit flipped on the KV
wire fails the connection with a named ``FrameCorruptError`` instead of
decoding silently wrong tokens.  Only the request's TRUE ``length``
columns travel: the bucket-padding garbage past ``length`` is masked or
overwritten before it is ever attended (the padded-prefill discipline),
so re-materializing it on the decode side as stale slot rows changes no
token.

Wire layout per request ``rid`` (tags are per (src, dst) pair, like the
reshard engine's fragment tags):

- ``kv/{rid}/m`` — int64 meta ``[length, first_tok, prefix_hit,
  prefill_ns, n_frames]``: the prefill rank samples the request's FIRST
  token itself (same ``sample_tokens`` math as the unified engine's
  prefill program) so the decode rank starts decoding with zero extra
  round-trips.
- ``kv/{rid}/{j}.{key}`` — layer ``j``'s ``key`` rows (``k``/``v``),
  shape ``(1, heads, head_dim, length)`` — the cache's own order, time
  last (``nn.cache.time_axis``) — in deterministic (sorted path, sorted
  key) order on both sides.

``wire="int8_blockN"`` opts each FLOAT fragment into the block-quantized
int8 wire from the collectives layer (PR 8): ~3.9x fewer bytes, but
LOSSY — the restored rows are not bit-identical to the computed ones, so
token parity with offline ``generate()`` no longer holds and the smoke
gate excludes it (same opt-in contract as the sharded partial-sum wire).
Integer fragments — the k/v rows of an int8 SLOT cache, already
quantized with their scales riding as separate float fragments — ship
exact regardless of ``wire``: re-quantizing integer data would be pure
loss, and both endpoints agree off the template's dtype.

Handle discipline: ``send(..., async_op=True)`` / ``fetch(...,
async_op=True)`` return a :class:`~tpu_dist.collectives.work.Work`
handle on the data plane's ordered engine — a dropped handle drops the
error a dead peer causes, which is exactly what tpudlint TD007 flags for
``<kv/xfer>.send/fetch``; the blocking :meth:`fetch` takes its deadline
positionally and is TD004-covered.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..nn import cache as kvcache
from .engine import ServeError

__all__ = ["KVTransfer", "KVTransferError", "kv_template"]

_META_FIELDS = 5   # length, first_tok, prefix_hit, prefill_ns, n_frames


class KVTransferError(ServeError):
    """A KV transfer could not complete (deadline passed, fragment/meta
    drift, wire mismatch) — names the request and the peer so the decode
    side can retry the prefill by name or fail the handle."""


# ``{layer_path: {key: (per_token_shape, dtype)}}`` from a slot-cache pool or
# a batch-1 row tree: the shape contract both transfer endpoints derive from
# their OWN model, so a fragment that arrives with drifted geometry is a
# named error, not a silent reshape
kv_template = kvcache.token_template


def _fragment_shape(per_token_shape: tuple, length: int) -> tuple:
    """Shape of one wire fragment: batch 1, the template's per-token
    shape, ``length`` columns — time last, as the cache stores it."""
    return (1,) + per_token_shape + (length,)


class KVTransfer:
    """Rank-addressed KV-row transfer over a
    :class:`~tpu_dist.collectives.transport.DataPlane`.

    ``template`` (see :func:`kv_template`) fixes the per-layer fragment
    geometry; both sides build it from their own model, so the tag order
    is deterministic without any negotiation.  ``wire=None`` ships exact
    dtype bytes; ``wire="int8_blockN"`` block-quantizes each fragment
    (lossy opt-in)."""

    def __init__(self, dp, template, wire=None):
        from ..collectives.quant import parse_scheme

        # fragments are the first ``length`` columns of every leaf
        kvcache.require_timed(template, "KVTransfer")
        self.dp = dp
        self.template = {path: dict(entry)
                         for path, entry in template.items()}
        self._frames: List[Tuple[str, str]] = [
            (path, key) for path in sorted(self.template)
            for key in sorted(self.template[path])]
        self.wire = parse_scheme(wire) if isinstance(wire, str) else wire
        if wire is not None and self.wire is None:
            raise KVTransferError(
                f"KV wire spec {wire!r} is not an int8_block{{N}} scheme "
                f"(the exact wire is wire=None)")
        self.sent_bytes = 0
        self.fetched_bytes = 0

    @staticmethod
    def _tag(rid: int, j: Optional[int] = None,
             key: Optional[str] = None) -> str:
        if j is None:
            return f"kv/{rid}/m"
        return f"kv/{rid}/{j}.{key}"

    def _quantized(self, path: str, key: str) -> bool:
        """Whether this fragment rides the int8_block wire: FLOAT
        fragments only.  An int8-slot-cache row's k/v are ALREADY int8
        with their scales travelling as separate (float, hence
        block-quantized) fragments — re-quantizing integer data would
        be pure loss.  Both endpoints evaluate this off the template's
        dtype, so the frame encodings agree without negotiation."""
        if self.wire is None:
            return False
        _, dtype = self.template[path][key]
        return np.issubdtype(dtype, np.floating)

    # -- prefill side ---------------------------------------------------------

    def send(self, dst: int, rid: int, rows, length: int, first_tok: int,
             prefix_hit: int = 0, prefill_ns: int = 0,
             async_op: bool = False):
        """Ship ``rows`` (per-layer batch-1 ``{"k","v"}`` trees, device or
        host) truncated to ``length`` columns to rank ``dst``.  Returns
        wire payload bytes sent; with ``async_op=True`` a Work handle
        (wait it — a dead decode rank's error is captured there)."""
        if async_op:
            from ..collectives.work import engine_for
            return engine_for(self.dp).submit(
                lambda: self.send(dst, rid, rows, length, first_tok,
                                  prefix_hit=prefix_hit,
                                  prefill_ns=prefill_ns),
                label=f"kv-send/{rid}")
        length = int(length)
        frags = []
        for path, key in self._frames:
            shape, dtype = self.template[path][key]
            arr = kvcache.time_slice(np.asarray(rows[path][key]), 0, length)
            if arr.shape != _fragment_shape(shape, length):
                raise KVTransferError(
                    f"kv send {rid}: layer {path!r}[{key}] rows have shape "
                    f"{arr.shape}, template expects "
                    f"{_fragment_shape(shape, length)} — the two endpoints' "
                    f"models disagree")
            frags.append(np.ascontiguousarray(arr, dtype))
        meta = np.asarray([length, int(first_tok), int(prefix_hit),
                           int(prefill_ns), len(frags)], np.int64)
        sent = self.dp.send_array(dst, self._tag(rid), meta)
        for j, ((path, key), arr) in enumerate(zip(self._frames, frags)):
            if self._quantized(path, key):
                from ..collectives.quant import QuantChunk, quantize
                q, scales = quantize(arr.reshape(-1), self.wire)
                sent += self.dp.send_quant(
                    dst, self._tag(rid, j, key),
                    QuantChunk(q, scales, self.wire))
            else:
                sent += self.dp.send_array(dst, self._tag(rid, j, key), arr)
        self.sent_bytes += int(sent)
        return int(sent)

    # -- decode side ----------------------------------------------------------

    def fetch(self, src: int, rid: int, timeout: float,
              async_op: bool = False):
        """Receive request ``rid``'s rows from rank ``src`` within
        ``timeout`` seconds (the whole transfer shares one deadline).
        Returns ``{"rows", "length", "first_tok", "prefix_hit",
        "prefill_ns", "bytes"}`` with host float rows ready for the slot
        injection program.  With ``async_op=True`` returns a Work handle
        resolving to the same dict.  A missed deadline raises
        :class:`KVTransferError` naming the request and peer; a dead peer
        surfaces as the data plane's named ``PeerGoneError``."""
        if async_op:
            from ..collectives.work import engine_for
            return engine_for(self.dp).submit(
                lambda: self.fetch(src, rid, timeout),
                label=f"kv-fetch/{rid}")
        deadline = time.monotonic() + float(timeout)

        def recv(tag):
            left = deadline - time.monotonic()
            if left <= 0:
                raise KVTransferError(
                    f"kv fetch {rid}: transfer from rank {src} missed its "
                    f"{float(timeout):.1f}s deadline (TPU_DIST_KV_TIMEOUT "
                    f"tunes it; a dead prefill rank raises PeerGoneError "
                    f"instead)")
            try:
                return self.dp.recv_array(src, tag, left)
            except KVTransferError:
                raise
            except TimeoutError as e:
                raise KVTransferError(
                    f"kv fetch {rid}: transfer from rank {src} missed its "
                    f"{float(timeout):.1f}s deadline waiting for "
                    f"{tag!r}: {e}") from e

        meta = np.asarray(recv(self._tag(rid)), np.int64).reshape(-1)
        if meta.size != _META_FIELDS:
            raise KVTransferError(
                f"kv fetch {rid}: meta frame has {meta.size} fields, "
                f"expected {_META_FIELDS} — sender/receiver version drift")
        length, first_tok, prefix_hit, prefill_ns, n_frames = (
            int(x) for x in meta)
        if n_frames != len(self._frames):
            raise KVTransferError(
                f"kv fetch {rid}: sender ships {n_frames} fragments, this "
                f"model expects {len(self._frames)} — layer layout drift")
        rows: Dict[str, Dict[str, np.ndarray]] = {}
        nbytes = int(meta.nbytes)
        for j, (path, key) in enumerate(self._frames):
            shape, dtype = self.template[path][key]
            got = recv(self._tag(rid, j, key))
            if self._quantized(path, key):
                nbytes += int(got.nbytes)
                got = got.dequantize(np.float32).astype(dtype, copy=False)
            else:
                nbytes += int(np.asarray(got).nbytes)
            arr = np.asarray(got).reshape(_fragment_shape(shape, length))
            rows.setdefault(path, {})[key] = arr
        self.fetched_bytes += nbytes
        return {"rows": rows, "length": length, "first_tok": first_tok,
                "prefix_hit": prefix_hit, "prefill_ns": prefill_ns,
                "bytes": nbytes}
