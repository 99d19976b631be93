"""Serving driver: batched prefill + decode with KV caches, optionally
retrieval-augmented (the paper's two-stage pipeline: the NDSearch engine
retrieves neighbor vectors that are prepended as soft-prompt embeddings).

  PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b --reduced \
      --batch 4 --prompt-len 64 --gen 32
  PYTHONPATH=src python -m repro.launch.serve --arch llava-next-mistral-7b \
      --reduced --rag --gen 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_config, reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as T


def make_step_fns(cfg, opts):
    """Jit prefill/decode once; reuse across warmup + timed runs so the
    reported tok/s excludes compile time."""
    prefill = jax.jit(lambda p, t, c, fe: T.prefill(
        p, cfg, t, c, opts=opts, frontend_embeds=fe))
    decode = jax.jit(lambda p, c, t: T.decode_step(p, cfg, c, t, opts=opts))
    return prefill, decode


def greedy_generate(params, cfg, tokens, *, gen: int, opts,
                    frontend_embeds=None, enc_len: int = 0, step_fns=None,
                    cache_len: int = 0):
    """``cache_len`` pins the KV-cache length (default Sp + gen) so a
    short warmup call can compile the exact shapes of a longer run."""
    B, Sp = tokens.shape
    cache = T.init_cache(cfg, B, cache_len or (Sp + gen),
                         enc_len=max(enc_len, 1), dtype=jnp.float32)
    prefill, decode = step_fns or make_step_fns(cfg, opts)
    logits, cache = prefill(params, tokens, cache, frontend_embeds)
    out = [jnp.argmax(logits, -1).astype(jnp.int32)[:, None]]
    for _ in range(gen - 1):
        logits, cache = decode(params, cache, out[-1])
        out.append(jnp.argmax(logits, -1).astype(jnp.int32)[:, None])
    return jnp.concatenate(out, axis=1)


def soft_prompt_from_retrieval(cfg, queries: np.ndarray, k: int = 4,
                               seed: int = 0, kernel_mode: str = "jnp",
                               coalesce_qb: int = 8,
                               streaming: bool = False):
    """Two-stage pipeline: NDSearch retrieval -> soft-prompt embeddings.

    Builds a small vector index, retrieves top-k neighbors of each query
    embedding with the distributed engine, and projects them into the
    model's embedding space. ``kernel_mode`` selects the retrieval
    hot-path backend (core/backend.py): inline jnp or the paged SiN
    distance + bitonic merge kernels; ``coalesce_qb`` is the kernel
    modes' per-page query-tile width. With ``streaming`` the batch goes
    through the streaming scheduler's slot pool (retrieval as a
    continuous-batching client, bit-identical results) instead of one
    frozen ``search_sim`` batch."""
    from repro.core.engine import EngineParams, pack_for_engine, search_sim
    from repro.core.luncsr import Geometry, LUNCSR, pack_index
    from repro.core.graph import build_vamana
    from repro.core.ref_search import SearchParams
    from repro.data.vectors import VectorDataset

    B, d = queries.shape
    ds = VectorDataset("serve-db", n=2048, dim=d, clusters=16, seed=seed)
    db = ds.materialize()
    adj, medoid = build_vamana(db, r=16, seed=seed)
    geom = Geometry(num_shards=1, page_size=64, pages_per_block=4, dim=d)
    idx = LUNCSR.from_adjacency(db, adj, geom, entry=medoid)
    packed = pack_index(idx, max_degree=16)
    if streaming:
        from repro.launch.serve_stream import StreamingRetriever
        retriever = StreamingRetriever(
            db, packed, L=16, W=1, k=k, num_slots=max(1, B // 2),
            kernel_mode=kernel_mode, coalesce_qb=coalesce_qb)
        vecs, ids, dists, _ = retriever.retrieve(
            np.asarray(queries, np.float32))
        return vecs, ids, dists
    consts, egeom, entry = pack_for_engine(packed)
    sp = SearchParams(L=16, W=1, k=k)
    params = EngineParams.lossless(sp, B, 16, kernel_mode=kernel_mode,
                                   coalesce_qb=coalesce_qb)
    ids, dists, _ = search_sim(
        consts, jnp.asarray(queries, jnp.float32)[None], *entry, params,
        egeom)
    ids = np.asarray(ids[0])
    vecs = db[np.clip(ids, 0, db.shape[0] - 1)]           # (B, k, d)
    return vecs, ids, np.asarray(dists[0])


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--rag", action="store_true",
                    help="two-stage: retrieve soft prompts via NDSearch")
    ap.add_argument("--rag-dim", type=int, default=32,
                    help="query-embedding dim of the RAG retrieval stage")
    ap.add_argument("--stream-retrieval", action="store_true",
                    help="route the RAG retrieval through the streaming "
                         "scheduler's slot pool (continuous batching) "
                         "instead of one frozen search_sim batch")
    ap.add_argument("--kernel-mode", default="jnp",
                    choices=["auto", "pallas", "interpret", "ref", "jnp"],
                    help="retrieval hot-path backend (core/backend.py)")
    ap.add_argument("--coalesce-qb", type=int, default=8,
                    help="kernel modes: per-page query-tile width for the "
                         "retrieval distance stage (0 = per-item)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    opts = T.ModelOpts(remat="none", loss_chunk=256)
    key = jax.random.PRNGKey(args.seed)
    params = T.init_params(cfg, key)
    tokens = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                cfg.vocab_size)

    fe = None
    enc_len = 0
    if cfg.frontend == "vision":
        fe = 0.05 * jax.random.normal(
            key, (args.batch, cfg.frontend_tokens, cfg.d_model))
    elif cfg.frontend == "audio":
        fe = 0.05 * jax.random.normal(
            key, (args.batch, args.prompt_len, cfg.d_model))
        enc_len = args.prompt_len
    elif args.rag:
        q = np.asarray(jax.random.normal(key, (args.batch, args.rag_dim)))
        # the soft prompt can't be wider than the prompt it overwrites
        vecs, ids, dists = soft_prompt_from_retrieval(
            cfg, q, k=max(1, min(4, args.prompt_len)),
            kernel_mode=args.kernel_mode, coalesce_qb=args.coalesce_qb,
            streaming=args.stream_retrieval)
        print("retrieved neighbor ids:", ids[:, :4].tolist())
        proj = np.asarray(jax.random.normal(
            jax.random.PRNGKey(7), (vecs.shape[-1], cfg.d_model))) * 0.02
        # soft prompt: the projected neighbor embeddings occupy the first
        # k prompt positions (decoder-only families included — prefill
        # overwrites the token embeddings for every non-encdec family)
        fe = jnp.asarray(vecs @ proj)                     # (B, k, d_model)

    # jit once, compile with a warmup generation (same cache shapes as
    # the full run), then time steady state
    step_fns = make_step_fns(cfg, opts)
    t0 = time.time()
    jax.block_until_ready(greedy_generate(
        params, cfg, tokens, gen=min(2, args.gen), opts=opts,
        frontend_embeds=fe, enc_len=enc_len, step_fns=step_fns,
        cache_len=args.prompt_len + args.gen))
    compile_s = time.time() - t0
    t0 = time.time()
    out = greedy_generate(params, cfg, tokens, gen=args.gen, opts=opts,
                          frontend_embeds=fe, enc_len=enc_len,
                          step_fns=step_fns)
    jax.block_until_ready(out)
    dt = time.time() - t0
    out = np.asarray(out)
    print(f"generated {out.shape} tokens in {dt:.2f}s "
          f"({args.batch * args.gen / dt:.1f} tok/s, excl. "
          f"{compile_s:.2f}s warmup/compile)")
    print("sample:", out[0, :16].tolist())
    assert np.isfinite(out).all()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
