"""Chip smoke test: the streaming ANN serving path, end to end on a TPU.

    python chip_smoke.py               # one chip: kernel check + phases A-C
    python chip_smoke.py --four-chips  # four chips: shard_map vs sim only

One chip. Builds the ``deep-1b`` stand-in index (big-ann-benchmarks DEEP
width: d=96 f32, L2, k=10) with the ``repro.launch.search`` builder, then
serves a Poisson stream of queries through ``search.serve`` — the code
``python -m repro.launch.search --stream`` runs — three times:

  A  ``--kernel-mode pallas``: the compiled SiN distance and bitonic
     kernels. recall@10 against exact float64 brute force must reach the
     CPU ``ref`` run's at the same seed less 0.01; every id is valid.
  B  ``--kernel-mode jnp``: inline XLA ops. recall within 0.01 of A; the
     id agreement with A is printed.
  C  A with the tiered page store at half residency: ids equal A's.

Four chips. The same data at n=4096 and four shards, served by the
shard_map stepper over a four-device mesh and by the single-device sim
driver; ids must match, and every device must have held its shard.

The script refuses to run anywhere but a TPU. The last line of stdout
is a JSON object with ``ok`` and the device JAX reports; any failed check
exits non-zero before it is printed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

DATASET = "deep-1b"
N = 16384
QUERIES = 256
ARRIVAL_RATE = 2.0        # Poisson arrivals per engine round
SEED = 0
# recall@10 of phase A's configuration with --kernel-mode ref, run on the
# CPU at the same seed (JAX_PLATFORMS=cpu, same N/QUERIES/ARRIVAL_RATE)
REF_RECALL = 0.546875
RECALL_SLACK = 0.01
MESH = 4                  # chips (and shards) of the four-chip phase
# the four-chip phase checks sharding and collectives, not scale: a
# smaller n keeps the host graph build (~2 min at N) off four held chips
N_MESH = 4096


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str):
    if not cond:
        raise SmokeFailure(what)


def require_tpu(count: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SmokeFailure(f"no TPU: JAX sees {devs[0].platform} devices")
    if len(devs) < count:
        raise SmokeFailure(f"needs {count} TPU chips, JAX sees {len(devs)}")
    return devs


def cli_args(kernel_mode: str, *extra: str, n: int = N):
    from repro.launch import search
    return search.parse_args([
        "--dataset", DATASET, "--n", str(n), "--queries", str(QUERIES),
        "--stream", "--arrival-rate", str(ARRIVAL_RATE),
        "--seed", str(SEED), "--kernel-mode", kernel_mode, *extra])


def valid_ids(ids: np.ndarray, n: int) -> bool:
    """Every id in range and no id twice in a query's top-k."""
    in_range = bool(((ids >= 0) & (ids < n)).all())
    distinct = all(len(set(row.tolist())) == row.size for row in ids)
    return in_range and distinct


def kernel_check(kernel_mode: str):
    """The two Pallas kernels against their ref oracles on real-valued
    data at the serving shapes: bitonic sort/merge bit-exact, distances
    to float32 rounding."""
    import jax.numpy as jnp

    from repro.core.backend import KernelBackend
    from repro.kernels.distance.ops import coalesced_distance_op

    rng = np.random.default_rng(SEED)
    kb, ref = KernelBackend(mode=kernel_mode), KernelBackend(mode="ref")
    rows, L, M = 64, 32, 16
    cd = jnp.asarray(np.sort(rng.standard_normal((rows, L)), axis=1),
                     jnp.float32)
    ci = jnp.asarray(rng.permutation(rows * L).reshape(rows, L), jnp.int32)
    ce = jnp.asarray(rng.integers(0, 2, (rows, L)).astype(bool))
    nd = jnp.asarray(rng.standard_normal((rows, M)), jnp.float32)
    ni = jnp.asarray(rows * L + rng.permutation(rows * M).reshape(rows, M),
                     jnp.int32)
    ne = jnp.zeros((rows, M), bool)
    got = kb.merge_unsorted(cd, ci, nd, ni, (ce,), (ne,))
    want = ref.merge_unsorted(cd, ci, nd, ni, (ce,), (ne,))
    for g, w in zip(got, want):
        check(np.array_equal(np.asarray(g), np.asarray(w)),
              "bitonic sort+merge differs from the ref oracle")

    NP, P, d, items = 32, 64, 96, 1024
    db = rng.standard_normal((NP, P, d)).astype(np.float32)
    vn = (db.astype(np.float64) ** 2).sum(-1).astype(np.float32)
    qv = rng.standard_normal((items, d)).astype(np.float32)
    qq = (qv.astype(np.float64) ** 2).sum(-1).astype(np.float32)
    pp = rng.integers(0, NP, items).astype(np.int32)
    sl = rng.integers(0, P, items).astype(np.int32)
    mask = rng.random(items) < 0.9
    exact = ((qv.astype(np.float64) - db[pp, sl].astype(np.float64)) ** 2
             ).sum(-1)
    worst = 0.0
    for qb in (1, 8):
        args = (pp, sl, mask, qv, qq, db, vn)
        out = np.asarray(coalesced_distance_op(*args, qb=qb,
                                               mode=kernel_mode))
        oref = np.asarray(coalesced_distance_op(*args, qb=qb, mode="ref"))
        scale = qq[mask] + vn[pp, sl][mask]
        err = np.abs(out[mask] - exact[mask]) / scale
        err_ref = np.abs(oref[mask] - exact[mask]) / scale
        worst = max(worst, float(err.max()))
        check(float(err.max()) < 1e-5,
              f"qb={qb}: distance error {err.max():.3g} of |q|^2+|v|^2")
        check(float(err_ref.max()) < 1e-5,
              f"qb={qb}: ref distance error {err_ref.max():.3g}")
    print(f"kernel check: bitonic merge bit-exact vs ref; distance max "
          f"error {worst:.3g} of |q|^2+|v|^2 (qb 1 and 8)")


def one_chip(kernel_mode: str = "pallas", ref_recall=REF_RECALL):
    from repro.core.engine import pack_for_engine
    from repro.core.graph import brute_force_topk, recall_at_k
    from repro.launch import search

    kernel_check(kernel_mode)

    args_a = cli_args(kernel_mode)
    built = search.build(args_a)
    consts, _, _ = pack_for_engine(built.packed)
    const_bytes = sum(int(x.nbytes) for x in consts.values())
    del consts
    n = int(built.db.shape[0])
    print(f"build: n={n} d={built.db.shape[1]} shards={args_a.shards} "
          f"in {built.build_s:.1f} s; engine consts {const_bytes} bytes "
          f"on device")
    truth, _ = brute_force_topk(built.db, built.queries, args_a.k)

    def phase(name, args):
        t0 = time.time()
        report, ids = search.serve(args, built)
        rec = recall_at_k(ids, truth)
        print(f"phase {name}: kernel_mode={args.kernel_mode} "
              f"device_pages={report['device_pages']} recall@10={rec} "
              f"rounds={report['total_rounds']} "
              f"dispatches={report['host_dispatches']} "
              f"compile_s={report['compile_s']} "
              f"phase_s={time.time() - t0:.1f}")
        check(valid_ids(ids, n), f"phase {name}: invalid or repeated ids")
        return rec, ids

    rec_a, ids_a = phase("A", args_a)
    if ref_recall is not None:
        check(rec_a >= ref_recall - RECALL_SLACK,
              f"phase A recall {rec_a} < CPU ref {ref_recall} - "
              f"{RECALL_SLACK}")

    rec_b, ids_b = phase("B", cli_args("jnp"))
    same_pos = float((ids_a == ids_b).mean())
    overlap = recall_at_k(ids_b, ids_a)
    print(f"A/B id agreement: {same_pos} of (query, rank) positions, "
          f"{overlap} of top-10 sets")
    check(abs(rec_a - rec_b) <= RECALL_SLACK,
          f"phase B recall {rec_b} vs A {rec_a}")

    half = built.packed.pages_per_shard // 2
    _, ids_c = phase("C", cli_args(kernel_mode, "--device-pages",
                                   str(half)))
    check(np.array_equal(ids_c, ids_a),
          f"phase C ids differ from A at "
          f"{int((ids_c != ids_a).any(axis=1).sum())} queries")
    print(f"phase C: ids equal phase A's at {half} of "
          f"{built.packed.pages_per_shard} pages per shard resident")


def four_chips(kernel_mode: str = "pallas"):
    """shard_map stepper on a four-device mesh vs the one-device sim
    driver, S=4 shards each, same stream."""
    import jax

    from repro.core.engine import EngineParams, pack_for_engine
    from repro.core.ref_search import SearchParams
    from repro.core.scheduler import poisson_arrivals, stream_search
    from repro.launch import search
    from repro.launch.mesh import make_engine_mesh

    args = cli_args(kernel_mode, "--shards", str(MESH), n=N_MESH)
    built = search.build(args)
    consts, geom, entry = pack_for_engine(built.packed)
    const_bytes = sum(int(x.nbytes) for x in consts.values())
    print(f"build: n={built.db.shape[0]} shards={args.shards} in "
          f"{built.build_s:.1f} s; engine consts {const_bytes} bytes")
    params = EngineParams.lossless(
        SearchParams(L=args.L, W=args.W, k=args.k), args.slots,
        built.packed.max_degree, kernel_mode=kernel_mode,
        coalesce_qb=args.coalesce_qb)
    arrivals = poisson_arrivals(ARRIVAL_RATE, QUERIES, SEED + 2)
    out = {}
    for name, mesh in (("sim", None),
                       ("shard_map", make_engine_mesh(num=MESH))):
        t0 = time.time()
        ids, _, st = stream_search(
            consts, geom, params, entry, built.queries,
            num_slots=args.slots, arrivals=arrivals,
            round_chunk=args.round_chunk, mesh=mesh)
        out[name] = ids
        print(f"{name}: rounds={st.total_rounds} "
              f"dispatches={st.host_dispatches} "
              f"wall_s={time.time() - t0:.1f}")
    check(np.array_equal(out["sim"], out["shard_map"]),
          f"shard_map ids differ from sim at "
          f"{int((out['sim'] != out['shard_map']).any(axis=1).sum())} "
          f"queries")
    devs = jax.devices()[:MESH]
    peaks = [d.memory_stats().get("peak_bytes_in_use", 0)
             if d.memory_stats() else 0 for d in devs]
    print(f"peak bytes in use per device: {peaks}")
    # each device held at least its shard of the vector store
    shard_bytes = int(consts["db"].nbytes) // MESH
    check(min(peaks) >= shard_bytes,
          f"a device never held its shard ({shard_bytes} bytes): {peaks}")
    print(f"shard_map on {MESH} devices == sim: ids equal for "
          f"{QUERIES} queries")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip shard_map vs sim phase")
    args = ap.parse_args(argv)
    count = MESH if args.four_chips else 1
    try:
        devs = require_tpu(count)
        from repro.launch.compile_cache import enable_compile_cache
        print(f"compile cache: {enable_compile_cache()}")
        print(f"device: {devs[0].device_kind} x{len(devs)}")
        if args.four_chips:
            four_chips()
        else:
            one_chip()
    except SmokeFailure as e:
        print(f"chip smoke FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
