"""On-disk artifact format (v2) and the LeafStore handle.

A saved index is a directory:

    meta.json      format version + the FrozenIndex static metadata,
                   array shapes, the raw-data dtype, and (v2) the leaf
                   payload ``codec``
    data.bin       [npad, payload_cols] leaf payload rows in the codec's
                   encoding, LEAF-CONTIGUOUS (row i of leaf l lives at
                   offsets[l] + i) — one leaf is one contiguous byte
                   range, so a leaf visit is a single sequential read
    exact.bin      (codec="pq" only) [npad, series_len] raw series in
                   the index dtype, same leaf-contiguous layout; read
                   only for the exact top-k re-rank and resident="full"
    sidecar.npz    box_lo / box_hi / weights / offsets / ids and the
                   distance-histogram edges/cdf (all small, device
                   resident at load time); for codec="pq" also the
                   trained PQ codebook (pq_centroids [m, K, dsub] and
                   pq_rotation [d, d]); since PR 3 also ``row_norms``
                   ([npad] f32 squared norms of the DECODED payload
                   rows) so search_ooc gathers cached norms instead of
                   re-reducing gathered rows every iteration (absent in
                   older sidecars -> recomputed at open, bit-identical
                   via ops.row_sq_norms)

Format v2 — pluggable leaf codecs.  ``codec`` selects the encoding of
``data.bin`` (the bytes the refinement stage streams from disk):

    "f32"   the index's native dtype verbatim (named for the common
            case; a bfloat16-built index stores bfloat16).  v1 bytes,
            bit-exact round trip.
    "bf16"  rows cast to bfloat16 — half the bytes-read per leaf; the
            decoded index is the bfloat16 image of the original, so
            resident="full" returns a bfloat16 FrozenIndex and
            search_ooc is bit-exact to in-memory search over it.
    "pq"    product-quantization codes (K=256, one uint8 per subspace,
            ``pq_m`` codes per row) — ~series_len*itemsize/pq_m x fewer
            bytes-read per leaf.  The codebook is trained at save time
            and persisted in the sidecar; search_ooc ADC-scores codes
            directly on device and exactly re-ranks the final top-k
            against ``exact.bin`` rows so the epsilon/delta-epsilon
            guarantee checks survive the lossy payload.

Version compatibility: v1 artifacts (no ``codec`` key) load read-only
with a :class:`StoreFormatDeprecationWarning` and behave as codec
"f32"; artifacts from a NEWER format version raise ``ValueError``
(scripts/verify.sh turns the deprecation warning into an error so the
repo's own tests never regenerate v1 stores).

``save_index`` persists any FrozenIndex; ``load_index`` either
reconstitutes the full device-resident FrozenIndex (resident="full")
or returns a :class:`LeafStore` (resident="summaries") that keeps only
the filter-stage state on device and opens ``data.bin`` via np.memmap
for the refinement stage to stream.
"""

from __future__ import annotations

import dataclasses
import json
import os
import warnings
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.histogram import DistanceHistogram
from repro.core.index import FrozenIndex
from repro.core.summaries.pq import PQCodebook, pq_encode, pq_train
from repro.kernels import ops

FORMAT_VERSION = 2
CODECS = ("f32", "bf16", "pq")
META_NAME = "meta.json"
DATA_NAME = "data.bin"
EXACT_NAME = "exact.bin"
SIDECAR_NAME = "sidecar.npz"
PQ_K = 256  # one uint8 code per subspace


class StoreFormatDeprecationWarning(DeprecationWarning):
    """Raised-as-warning when reading a pre-v2 store artifact."""


def _default_pq_m(series_len: int) -> int:
    for m in (16, 8, 4, 2, 1):
        if series_len % m == 0:
            return m
    return 1


_ENCODE_ROWS = 1 << 18  # rows per pq_encode call in save_index


def save_index(
    index: FrozenIndex,
    directory: str,
    *,
    codec: str = "f32",
    pq_m: Optional[int] = None,
    pq_iters: int = 6,
    pq_train_rows: int = 8192,
    pq_key: Optional[jax.Array] = None,
) -> str:
    """Persist ``index`` under ``directory`` (created if missing).

    ``codec`` selects the data.bin leaf payload encoding (module
    docstring); ``pq_*`` tune the codebook trained for codec="pq"
    (``pq_m`` sub-quantizers — must divide series_len, default the
    largest of 16/8/4/2 that does — over at most ``pq_train_rows``
    sampled rows).
    """
    if codec not in CODECS:
        raise ValueError(f"codec must be one of {CODECS}, got {codec!r}")
    os.makedirs(directory, exist_ok=True)
    data = np.asarray(index.data)
    meta = {
        "format_version": FORMAT_VERSION,
        "codec": codec,
        "kind": index.kind,
        "summary": index.summary,
        "n_summary": index.n_summary,
        "max_leaf": index.max_leaf,
        "n_total": index.n_total,
        "series_len": index.series_len,
        "npad": int(data.shape[0]),
        "n_leaves": int(index.num_leaves),
        "n_dims": int(index.box_lo.shape[1]),
        "data_dtype": str(jnp.dtype(index.data.dtype)),
    }
    sidecar = dict(
        box_lo=np.asarray(index.box_lo),
        box_hi=np.asarray(index.box_hi),
        weights=np.asarray(index.weights),
        offsets=np.asarray(index.offsets),
        ids=np.asarray(index.ids),
        hist_edges=np.asarray(index.hist.edges),
        hist_cdf=np.asarray(index.hist.cdf),
    )
    # squared norms of the DECODED payload rows: what the reloaded
    # index (resident="full") and search_ooc's refine gathers both use,
    # so they stay bit-identical to the in-memory search over the same
    # decoded image. f32/pq decode to the index's own rows — reuse the
    # freeze-time cache when present; bf16 decodes to the bfloat16
    # image, whose norms differ from the f32 rows'.
    if codec == "bf16":
        sidecar["row_norms"] = np.asarray(ops.row_sq_norms(
            jnp.asarray(data, jnp.bfloat16)))
    elif index.row_norms is not None:
        sidecar["row_norms"] = np.asarray(index.row_norms)
    else:
        sidecar["row_norms"] = np.asarray(ops.row_sq_norms(
            jnp.asarray(data)))
    if codec == "f32":
        payload = data
    elif codec == "bf16":
        payload = np.asarray(jnp.asarray(data, jnp.bfloat16))
    else:  # pq
        m = _default_pq_m(index.series_len) if pq_m is None else int(pq_m)
        if index.series_len % m:
            raise ValueError(
                f"pq_m={m} must divide series_len={index.series_len}")
        key = pq_key if pq_key is not None else jax.random.PRNGKey(0)
        ids = np.asarray(index.ids)
        rows = np.asarray(data[ids >= 0], np.float32)
        if rows.shape[0] > pq_train_rows:
            sel = np.random.default_rng(0).choice(
                rows.shape[0], pq_train_rows, replace=False)
            rows = rows[sel]
        cb = pq_train(key, jnp.asarray(rows), m, k=PQ_K, iters=pq_iters)
        # encoded in row chunks: pq_encode materializes an [N, PQ_K]
        # distance matrix per subspace, which at 4M rows is more HBM
        # than one v5e chip has
        payload = np.concatenate([
            np.asarray(pq_encode(cb, jnp.asarray(
                data[lo:lo + _ENCODE_ROWS], jnp.float32)), np.uint8)
            for lo in range(0, data.shape[0], _ENCODE_ROWS)])
        meta["pq_m"] = m
        sidecar["pq_centroids"] = np.asarray(cb.centroids, np.float32)
        sidecar["pq_rotation"] = np.asarray(cb.rotation, np.float32)
        data.tofile(os.path.join(directory, EXACT_NAME))
    meta["payload_dtype"] = str(jnp.dtype(payload.dtype))
    meta["payload_cols"] = int(payload.shape[1])
    payload.tofile(os.path.join(directory, DATA_NAME))
    np.savez(os.path.join(directory, SIDECAR_NAME), **sidecar)
    with open(os.path.join(directory, META_NAME), "w") as f:
        json.dump(meta, f, indent=1)
    return directory


@dataclasses.dataclass
class LeafStore:
    """Out-of-core residency: filter state on device, payload on disk.

    ``resident`` is a FrozenIndex whose ``data`` child is an EMPTY
    [0, series_len] placeholder — everything the filter stage (lower
    bounds, visit order, r_delta) and the id lookup of the refinement
    stage need is device resident; the ENCODED leaf payload is only
    reachable through ``mmap`` (or a DeviceLeafCache layered on top of
    it), and for codec="pq" the raw series additionally through
    ``exact_mmap`` (re-rank reads only).
    """

    directory: str
    resident: FrozenIndex
    mmap: np.memmap          # [npad, payload_cols], leaf-contiguous
    meta: dict
    offsets_h: np.ndarray    # [L+1] int64 host copy for disk reads
    codec: str = "f32"
    exact_mmap: Optional[np.memmap] = None   # pq only: raw rows
    codebook: Optional[PQCodebook] = None    # pq only: device arrays

    @property
    def num_leaves(self) -> int:
        return self.resident.num_leaves

    @property
    def max_leaf(self) -> int:
        return self.resident.max_leaf

    @property
    def series_len(self) -> int:
        return self.resident.series_len

    @property
    def data_dtype(self) -> np.dtype:
        """Dtype of the ENCODED payload rows (what slots/buffers hold)."""
        return self.mmap.dtype

    @property
    def payload_cols(self) -> int:
        """Columns per encoded payload row (= series_len, or pq_m)."""
        return self.mmap.shape[1]

    @property
    def dataset_nbytes(self) -> int:
        """Size of the RAW collection (exact rows in the index dtype),
        NOT the encoded payload — so %-data metrics stay comparable
        across codecs (bf16's payload is half this; pq's far less)."""
        itemsize = np.dtype(jnp.dtype(self.meta["data_dtype"])).itemsize
        return int(self.mmap.shape[0]) * self.series_len * itemsize

    def leaf_size(self, leaf: int) -> int:
        return int(self.offsets_h[leaf + 1] - self.offsets_h[leaf])

    def read_leaf(self, leaf: int, out: np.ndarray = None) -> np.ndarray:
        """One leaf's ENCODED rows, padded to [max_leaf, payload_cols].

        A single contiguous range of ``data.bin`` — the sequential-read
        unit the paper's on-disk evaluation is about. When ``out`` is
        reused across reads, rows past this leaf's size are zeroed so a
        previously resident larger leaf never leaks stale rows.
        """
        lo = int(self.offsets_h[leaf])
        hi = int(self.offsets_h[leaf + 1])
        if out is None:
            out = np.zeros((self.max_leaf, self.payload_cols),
                           self.mmap.dtype)
        else:
            out[hi - lo:] = 0
        out[: hi - lo] = self.mmap[lo:hi]
        return out

    def read_rows_exact(self, positions: np.ndarray) -> np.ndarray:
        """Raw (exact-dtype) rows by padded row position — the pq
        re-rank path. Tiny random reads; callers account the bytes."""
        src = self.exact_mmap if self.exact_mmap is not None else self.mmap
        return np.asarray(src[np.asarray(positions, np.int64)])

    def leaf_nbytes(self, leaf: int) -> int:
        return self.leaf_size(leaf) * self.payload_cols \
            * self.mmap.dtype.itemsize


def load_index(
    directory: str, resident: str = "full"
) -> Union[FrozenIndex, LeafStore]:
    """Open a saved index. resident="full" -> FrozenIndex (bit-exact
    round trip for codec f32/pq, the bfloat16 image for codec bf16);
    resident="summaries" -> LeafStore (payload stays on disk)."""
    with open(os.path.join(directory, META_NAME)) as f:
        meta = json.load(f)
    ver = meta["format_version"]
    if ver > FORMAT_VERSION:
        raise ValueError(
            f"store format {ver} is newer than this reader "
            f"(supports <= {FORMAT_VERSION}); upgrade the code")
    if ver < FORMAT_VERSION:
        warnings.warn(
            f"store format {ver} at {directory!r} is deprecated "
            f"(current: {FORMAT_VERSION}); re-save with save_index to "
            "upgrade", StoreFormatDeprecationWarning, stacklevel=2)
    codec = meta.get("codec", "f32")
    side = np.load(os.path.join(directory, SIDECAR_NAME))
    dtype = jnp.dtype(meta["data_dtype"])
    payload_dtype = jnp.dtype(meta.get("payload_dtype",
                                       meta["data_dtype"]))
    payload_cols = int(meta.get("payload_cols", meta["series_len"]))
    hist = DistanceHistogram(
        edges=jnp.asarray(side["hist_edges"]),
        cdf=jnp.asarray(side["hist_cdf"]),
    )
    statics = dict(
        kind=meta["kind"], summary=meta["summary"],
        n_summary=meta["n_summary"], max_leaf=meta["max_leaf"],
        n_total=meta["n_total"], series_len=meta["series_len"],
    )
    mmap = np.memmap(
        os.path.join(directory, DATA_NAME),
        dtype=np.dtype(payload_dtype),
        mode="r", shape=(meta["npad"], payload_cols),
    )
    exact_mmap = None
    codebook = None
    if codec == "pq":
        exact_mmap = np.memmap(
            os.path.join(directory, EXACT_NAME), dtype=np.dtype(dtype),
            mode="r", shape=(meta["npad"], meta["series_len"]),
        )
        codebook = PQCodebook(
            centroids=jnp.asarray(side["pq_centroids"]),
            rotation=jnp.asarray(side["pq_rotation"]),
        )
    def decoded_norms(chunk_rows: int = 65536):
        """Pre-PR3 sidecars lack row_norms: recompute from the decoded
        rows with the same op the freeze/save paths use. Chunked so a
        summaries-resident open of a legacy store never materializes
        the whole payload on device (row-wise sums are independent of
        the chunking, so the result stays bit-identical)."""
        src = exact_mmap if codec == "pq" else mmap
        out = np.empty(src.shape[0], np.float32)
        for lo in range(0, src.shape[0], chunk_rows):
            hi = min(lo + chunk_rows, src.shape[0])
            out[lo:hi] = np.asarray(
                ops.row_sq_norms(jnp.asarray(np.asarray(src[lo:hi]))))
        return jnp.asarray(out)

    row_norms = (jnp.asarray(side["row_norms"])
                 if "row_norms" in side else decoded_norms())
    if resident == "full":
        if codec == "pq":
            full_rows = jnp.asarray(np.asarray(exact_mmap), dtype)
        elif codec == "bf16":
            full_rows = jnp.asarray(np.asarray(mmap))  # bfloat16 image
        else:
            full_rows = jnp.asarray(np.asarray(mmap), dtype)
        return FrozenIndex(
            box_lo=jnp.asarray(side["box_lo"]),
            box_hi=jnp.asarray(side["box_hi"]),
            weights=jnp.asarray(side["weights"]),
            offsets=jnp.asarray(side["offsets"]),
            data=full_rows,
            ids=jnp.asarray(side["ids"]),
            hist=hist,
            row_norms=row_norms,
            **statics,
        )
    if resident != "summaries":
        raise ValueError("resident must be 'full' or 'summaries', "
                         f"got {resident!r}")
    placeholder = jnp.zeros((0, meta["series_len"]), dtype)
    res = FrozenIndex(
        box_lo=jnp.asarray(side["box_lo"]),
        box_hi=jnp.asarray(side["box_hi"]),
        weights=jnp.asarray(side["weights"]),
        offsets=jnp.asarray(side["offsets"]),
        data=placeholder,
        ids=jnp.asarray(side["ids"]),
        hist=hist,
        row_norms=row_norms,
        **statics,
    )
    return LeafStore(
        directory=directory,
        resident=res,
        mmap=mmap,
        meta=meta,
        offsets_h=np.asarray(side["offsets"], np.int64),
        codec=codec,
        exact_mmap=exact_mmap,
        codebook=codebook,
    )
