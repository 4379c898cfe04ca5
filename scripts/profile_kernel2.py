#!/usr/bin/env python
"""Honest kernel profile: force with a device->host readback (the
consumer-side completion point), and separate per-call dispatch cost
from device compute by looping the kernel inside ONE jit via lax.scan
with a data dependence between iterations.
"""
import argparse
import os
import sys
import time
from functools import partial

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def force_time(fn, args, iters):
    """Enqueue iters calls back-to-back, force via readback of the last
    result; returns seconds/iter (bench.py methodology)."""
    r = fn(*args)
    np.asarray(r[0])  # warm + sync
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        rs = [fn(*args) for _ in range(iters)]
        np.asarray(rs[-1][0])
        best = min(best, (time.perf_counter() - t0) / iters)
    return best


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--filters", type=int, default=200_000)
    ap.add_argument("--batch", type=int, default=8192)
    ap.add_argument("--depth", type=int, default=8)
    ap.add_argument("--iters", type=int, default=30)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from bench import build_workload
    from emqx_tpu.ops import compile_filters, encode_topics
    from emqx_tpu.ops.match_kernel import nfa_match

    rng = np.random.default_rng(42)
    filters, topics = build_workload(rng, args.filters, args.batch, args.depth)
    t0 = time.perf_counter()
    table = compile_filters(filters, depth=args.depth)
    print(f"compile {time.perf_counter()-t0:.1f}s states={table.n_states} "
          f"S={table.node_tab.shape[0]} Hb={table.edge_tab.shape[0]}")
    words, lens, is_sys = encode_topics(table, topics[: args.batch],
                                        batch=args.batch)
    arrs = [jnp.asarray(a) for a in table.device_arrays()]
    dev_args = (jnp.asarray(words), jnp.asarray(lens), jnp.asarray(is_sys),
                *arrs)

    B = args.batch
    ms = force_time(
        lambda *a: nfa_match(*a, active_slots=16, max_matches=32).matches[
            None], dev_args, args.iters) * 1e3
    print(f"single-call A=16: {ms:7.2f} ms/batch  "
          f"{B/ms*1e3/1e6:.2f}M t/s")

    # device-side loop: N kernel runs inside one jit, chained so XLA
    # can't elide them; isolates device compute from dispatch cost
    N = 16

    @jax.jit
    def looped(words, lens, is_sys, node, edge, seeds):
        def body(carry, _):
            w = jnp.bitwise_xor(words, carry)  # cheap data dependence
            r = nfa_match(w, lens, is_sys, node, edge, seeds,
                          active_slots=16, max_matches=32)
            return (carry + r.n_matches[0]) % 2, r.n_matches

        c, outs = jax.lax.scan(body, jnp.int32(0), None, length=N)
        return outs

    r = looped(*dev_args)
    np.asarray(r)
    t0 = time.perf_counter()
    r = looped(*dev_args)
    np.asarray(r)
    per = (time.perf_counter() - t0) / N * 1e3
    print(f"device-looped x{N}: {per:7.2f} ms/batch (pure device)  "
          f"{B/per*1e3/1e6:.2f}M t/s")

    for A in (4, 8, 32):
        ms = force_time(
            lambda *a: nfa_match(*a, active_slots=A, max_matches=32).matches[
                None], dev_args, args.iters) * 1e3
        print(f"single-call A={A:2d}: {ms:7.2f} ms/batch  "
              f"{B/ms*1e3/1e6:.2f}M t/s")

    for B2 in (16384, 32768):
        tt = (topics * ((B2 // len(topics)) + 1))[:B2]
        w2, l2, s2 = encode_topics(table, tt, batch=B2)
        a2 = (jnp.asarray(w2), jnp.asarray(l2), jnp.asarray(s2), *arrs)
        ms = force_time(
            lambda *a: nfa_match(*a, active_slots=16, max_matches=32).matches[
                None], a2, args.iters) * 1e3
        print(f"batch={B2:6d} A=16: {ms:7.2f} ms/batch  "
              f"{B2/ms*1e3/1e6:.2f}M t/s")


if __name__ == "__main__":
    main()
