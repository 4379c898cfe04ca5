#!/usr/bin/env python
"""Pure-device ablation: run N chained kernel iterations inside ONE jit
(lax.scan, data dependence) so dispatch cost amortizes away, and
ablate each component of the v2 walk at A=8.
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp


def devtime(fn, args, N=16):
    f = jax.jit(fn)
    r = f(*args)
    np.asarray(jax.tree_util.tree_leaves(r)[0])
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        r = f(*args)
        np.asarray(jax.tree_util.tree_leaves(r)[0])
        best = min(best, (time.perf_counter() - t0) / N)
    return best * 1e3


def make_looped(kernel_step, N=16):
    def looped(words, lens, is_sys, node, edge, seeds):
        def body(carry, _):
            w = jnp.bitwise_xor(words, carry)
            out = kernel_step(w, lens, is_sys, node, edge, seeds)
            return (carry + out[0][0]) % 2, out
        c, outs = jax.lax.scan(body, jnp.int32(0), None, length=N)
        return outs
    return looped


def variant(D, A, K, *, edges=True, node_g=True, per_step=True, final=True,
            seeds_n=2):
    from emqx_tpu.ops.match_kernel import _edge_lookup, _compact

    def run(words, lens, is_sys, node_tab, edge_tab, seeds):
        B = words.shape[0]
        active = jnp.zeros((B, 1), jnp.int32)
        accept_cols = []
        for t in range(D + 1):
            valid = active >= 0
            sa = jnp.maximum(active, 0)
            if node_g:
                node = node_tab[sa]
            else:
                node = jnp.stack([sa, sa, sa, sa], axis=-1)  # fake, no gather
            hacc = jnp.where(valid, node[..., 1], -1)
            if t == 0:
                hacc = jnp.where(is_sys[:, None], -1, hacc)
            at_end = (t == lens)[:, None]
            eacc = jnp.where(valid & at_end, node[..., 2], -1)
            accept_cols.append(jnp.concatenate([hacc, eacc], axis=1))
            if t == D:
                break
            w = jnp.broadcast_to(words[:, t][:, None], active.shape)
            if edges:
                lit = _edge_lookup(active, w, edge_tab, seeds)
            else:
                lit = jnp.where(w > 0, node[..., 0], -1)  # fake, no gather
            lit = jnp.where(valid, lit, -1)
            plus = jnp.where(valid, node[..., 0], -1)
            if t == 0:
                plus = jnp.where(is_sys[:, None], -1, plus)
            cand = jnp.concatenate([lit, plus], axis=1)
            cand = jnp.where((t < lens)[:, None], cand, -1)
            if cand.shape[1] <= A:
                active = cand
            elif per_step:
                active, _ = jax.lax.top_k(cand, A)
            else:
                active = cand[:, :A]  # fake, wrong semantics
        flat = jnp.concatenate(accept_cols, axis=1)
        n = jnp.sum((flat >= 0).astype(jnp.int32), axis=1)
        if final:
            m = _compact(flat, K)
        else:
            m = flat[:, :K]
        return n, m

    return run


def main():
    from bench import build_workload
    from emqx_tpu.ops import compile_filters, encode_topics

    rng = np.random.default_rng(42)
    B, D = 8192, 8
    filters, topics = build_workload(rng, 200_000, B, D)
    t0 = time.perf_counter()
    table = compile_filters(filters, depth=D)
    print(f"compile {time.perf_counter()-t0:.1f}s states={table.n_states}")
    words, lens, is_sys = encode_topics(table, topics[:B], batch=B)
    args = (jnp.asarray(words), jnp.asarray(lens), jnp.asarray(is_sys),
            *[jnp.asarray(a) for a in table.device_arrays()])

    A = 8
    for name, kw in [
        ("full v2 A=8", {}),
        ("  -edge gathers", dict(edges=False)),
        ("  -node gathers", dict(node_g=False)),
        # ("  -per-step topk", dict(per_step=False)),
        # ("  -final compact", dict(final=False)),
        ("  bare (no gathers/compact)",
         dict(edges=False, node_g=False, per_step=False, final=False)),
    ]:
        fn = make_looped(variant(D, A, 32, **kw))
        ms = devtime(fn, args)
        print(f"{name:28s}: {ms:6.2f} ms/iter  {B/ms*1e3/1e6:.2f}M t/s")

    for A2 in ():
        fn = make_looped(variant(D, A2, 32))
        ms = devtime(fn, args)
        print(f"full v2 A={A2:<2d}                 : {ms:6.2f} ms/iter  "
              f"{B/ms*1e3/1e6:.2f}M t/s")


if __name__ == "__main__" and not os.environ.get("SWEEP"):
    main()


def batch_sweep():
    from bench import build_workload
    from emqx_tpu.ops import compile_filters, encode_topics
    rng = np.random.default_rng(42)
    D = 8
    filters, topics = build_workload(rng, 200_000, 65536, D)
    table = compile_filters(filters, depth=D)
    print(f"states={table.n_states}")
    arrs = [jnp.asarray(a) for a in table.device_arrays()]
    for B in (8192, 32768, 65536, 131072):
        tt = (topics * ((B // len(topics)) + 1))[:B]
        w, l, s = encode_topics(table, tt, batch=B)
        args = (jnp.asarray(w), jnp.asarray(l), jnp.asarray(s), *arrs)
        N = 8
        fn = make_looped(variant(D, 8, 32), N=N)
        ms = devtime(fn, args, N=N)
        print(f"B={B:6d} A=8 pure-device: {ms:7.2f} ms/iter  "
              f"{B/ms*1e3/1e6:.2f}M t/s")


if __name__ == "__main__" and os.environ.get("SWEEP"):
    batch_sweep()
