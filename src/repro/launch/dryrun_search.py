import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# XLA device-count override must precede any jax import (see dryrun.py).

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.obs import now  # noqa: E402
from repro.core.histogram import DistanceHistogram  # noqa: E402
from repro.core.index import FrozenIndex  # noqa: E402
from repro.core.search import SearchResult, search_impl  # noqa: E402
from repro.launch import roofline as roof  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402

"""Production dry-run of the paper's own technique: a billion-series
DistributedSearchEngine query lowered + compiled on the 256/512-chip
meshes (this is the cell the §Perf loop hillclimbs as "most
representative of the paper").

Configuration mirrors the paper's disk-scale setting, scaled to pod HBM:
per-shard 2M series x 256 f32 (2 GB/chip), leaf_cap 512, batched 256
queries, k=100, ng(nprobe) visits — 512 chips hold 1.02B series, i.e.
the Deep1B/Sift1B regime the paper calls the largest public datasets.
"""


def abstract_index(mesh, axes, n_per_shard: int, series_len: int,
                   leaf_cap: int, summary: str = "eapca"):
    shards = 1
    for a in axes:
        shards *= dict(zip(mesh.axis_names, mesh.devices.shape))[a]
    leaves = n_per_shard // leaf_cap
    dims = {"paa": 16, "eapca": 16, "dft": 16}[summary]
    spec0 = P(axes if len(axes) > 1 else axes[0])

    def sds(shape, spec):
        return jax.ShapeDtypeStruct(
            shape, jnp.float32, sharding=NamedSharding(mesh, spec))

    def sdsi(shape, spec):
        return jax.ShapeDtypeStruct(
            shape, jnp.int32, sharding=NamedSharding(mesh, spec))

    idx = FrozenIndex(
        box_lo=sds((shards, leaves, dims), spec0),
        box_hi=sds((shards, leaves, dims), spec0),
        weights=sds((dims,), P()),
        offsets=sdsi((shards, leaves + 1), spec0),
        data=sds((shards, n_per_shard, series_len), spec0),
        ids=sdsi((shards, n_per_shard), spec0),
        hist=DistanceHistogram(edges=sds((513,), P()),
                               cdf=sds((513,), P())),
        kind="dstree", summary=summary, n_summary=8,
        max_leaf=leaf_cap, n_total=n_per_shard * shards,
        series_len=series_len,
        # cached |x|^2 (PR 3): the refinement loop gathers norms
        # instead of re-reducing gathered rows each iteration
        row_norms=sds((shards, n_per_shard), spec0),
    )
    return idx, shards, leaves


def lower_search(mesh, *, n_per_shard=2_000_000, series_len=256,
                 leaf_cap=512, batch=256, k=100, nprobe=128,
                 visit_batch=8, data_bf16=False, coop=False):
    # pure search has no tensor dimension to 'model'-parallelize: every
    # chip owns a DB shard — 256 shards x 2M = 512M series single-pod,
    # 512 x 2M = 1.02B multi-pod (the paper's Deep1B/Sift1B scale)
    axes = tuple(mesh.axis_names)
    idx, shards, leaves = abstract_index(
        mesh, axes, n_per_shard, series_len, leaf_cap)
    if data_bf16:
        import dataclasses as _dc
        import jax.numpy as _jnp
        old = idx.data
        idx = _dc.replace(idx, data=jax.ShapeDtypeStruct(
            old.shape, _jnp.bfloat16, sharding=old.sharding))
    q_sds = jax.ShapeDtypeStruct(
        (batch, series_len), jnp.float32,
        sharding=NamedSharding(mesh, P()))
    spec0 = P(axes if len(axes) > 1 else axes[0])
    in_specs = (
        FrozenIndex(
            box_lo=spec0, box_hi=spec0, offsets=spec0, data=spec0,
            ids=spec0, weights=P(),
            hist=DistanceHistogram(edges=P(), cdf=P()),
            kind=idx.kind, summary=idx.summary, n_summary=idx.n_summary,
            max_leaf=idx.max_leaf, n_total=idx.n_total,
            series_len=idx.series_len, row_norms=spec0,
        ),
        P(),
    )

    def local(idx_local, q):
        sq = jax.tree_util.tree_map(
            lambda a: a[0], (idx_local.box_lo, idx_local.box_hi,
                             idx_local.offsets, idx_local.data,
                             idx_local.ids, idx_local.row_norms))
        lidx = dataclasses.replace(
            idx_local, box_lo=sq[0], box_hi=sq[1], offsets=sq[2],
            data=sq[3], ids=sq[4], row_norms=sq[5])
        # repro: allow[jax-while-shard-map] compile-only roofline dry run: the jitted executable is lowered and cost-analyzed, never executed, so the 0.4.37 runtime miscompile cannot produce wrong numbers here
        res = search_impl(lidx, q, k, nprobe=nprobe,
                          visit_batch=visit_batch,
                          share_gathers=coop)
        all_d = res.dists
        all_i = res.ids
        for ax in axes:
            all_d = jax.lax.all_gather(all_d, ax, tiled=False)
            all_i = jax.lax.all_gather(all_i, ax, tiled=False)
        all_d = all_d.reshape(-1, batch, k).transpose(1, 0, 2) \
            .reshape(batch, -1)
        all_i = all_i.reshape(-1, batch, k).transpose(1, 0, 2) \
            .reshape(batch, -1)
        sd, si = jax.lax.sort((all_d, all_i), num_keys=1)
        return SearchResult(sd[:, :k], si[:, :k],
                            jax.lax.psum(res.leaves_visited, axes),
                            jax.lax.psum(res.rows_scanned, axes),
                            jax.lax.psum(res.lb_computed, axes))

    fn = jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                       out_specs=SearchResult(P(), P(), P(), P(), P()),
                       check_vma=False)
    t0 = now()
    lowered = jax.jit(fn).lower(idx, q_sds)
    compiled = lowered.compile()
    t_compile = now() - t0

    world = mesh.devices.size
    # analytic terms (per shard, data-dependent loop bounded by nprobe)
    visited_rows = nprobe * leaf_cap
    # cooperative batching: measured 25% fewer gathers at exact, and
    # every gathered row is scored by all B lanes (one MXU matmul)
    gather_eff = 0.75 if coop else 1.0
    score_mult = batch if coop else 1.0
    dbytes = 2.0 if data_bf16 else 4.0
    flops_shard = (
        batch * leaves * idx.n_summary * 4.0          # box lb pass
        + gather_eff * batch * visited_rows * series_len * 2.0
        * score_mult                                  # refinement L2
    )
    bytes_shard = (
        leaves * idx.n_summary * 2 * 4.0              # boxes
        + gather_eff * batch * visited_rows * series_len * dbytes
    )
    chips_per_shard = world / (idx.box_lo.shape[0])
    rep = roof.roofline_report(
        compiled, world=world,
        model_flops_global=flops_shard * idx.box_lo.shape[0],
        analytic_flops_global=flops_shard * idx.box_lo.shape[0],
        analytic_bytes_global=bytes_shard * idx.box_lo.shape[0],
        steps_hint=f"search nprobe={nprobe} vb={visit_batch} "
                   f"chips/shard={chips_per_shard:.0f}",
    )
    rep.update({
        "arch": "search-engine", "shape": f"scan_n{n_per_shard}",
        "status": "ok", "mesh": list(mesh.devices.shape),
        "mesh_axes": list(mesh.axis_names),
        "compile_seconds": round(t_compile, 1),
        "n_total_series": idx.n_total,
    })
    print(compiled.memory_analysis())
    ca = compiled.cost_analysis() or {}
    print({kk: ca[kk] for kk in ("flops", "bytes accessed") if kk in ca})
    return rep


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--n-per-shard", type=int, default=2_000_000)
    ap.add_argument("--nprobe", type=int, default=128)
    ap.add_argument("--bf16-data", action="store_true")
    ap.add_argument("--coop", action="store_true")
    ap.add_argument("--tag", default="scan")
    args = ap.parse_args()
    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("single_pod_16x16", make_production_mesh()))
    if args.mesh in ("multi", "both"):
        meshes.append(("multi_pod_2x16x16",
                       make_production_mesh(multi_pod=True)))
    for name, mesh in meshes:
        outdir = os.path.join(args.out, name)
        os.makedirs(outdir, exist_ok=True)
        print(f"=== {name} :: search-engine ===", flush=True)
        with mesh:
            rep = lower_search(mesh, n_per_shard=args.n_per_shard,
                               nprobe=args.nprobe,
                               data_bf16=args.bf16_data, coop=args.coop)
        with open(os.path.join(outdir, f"search-engine__{args.tag}.json"),
                  "w") as f:
            json.dump(rep, f, indent=2, default=str)
        t = rep["terms_seconds"]
        print(f"ok compile={rep['compile_seconds']}s "
              f"compute={t['compute']:.4f}s memory={t['memory']:.4f}s "
              f"coll={t['collective']:.4f}s "
              f"bottleneck={rep['bottleneck']} "
              f"series={rep['n_total_series']:,}", flush=True)


if __name__ == "__main__":
    main()
