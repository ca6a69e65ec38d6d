"""Device correctness check: pallas_ops vs the XLA table ops.

Runs randomized op batches through both implementations and compares
bit-exactly. The CPU test suite cannot exercise the pallas path (Mosaic
is TPU-only), so this is the TPU-side parity gate — run it on the chip
whenever pallas_ops changes:

    python benchmarks/pallas_ops_check.py
"""

import sys

import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from zeebe_tpu.tpu import hashmap, pallas_ops as pops  # noqa: E402


def check(name, a, b):
    a, b = np.asarray(a), np.asarray(b)
    if not (a == b).all():
        bad = np.argwhere(a != b)[:5]
        raise SystemExit(f"MISMATCH {name}: {bad}\n{a.ravel()[:8]} vs {b.ravel()[:8]}")
    print(f"ok: {name}")


def check_fused_commit(rng, T, B):
    """Mega-pass parity: fused_table_commit (one pallas launch) vs the
    unfused XLA op chain, over the kernel's real op mix — masked/blind row
    sets on disjoint writer sets, commutative adds/maxes with duplicates,
    and 1D lane writes (free rings / direct-mapped indexes)."""
    K = 16
    assert T >= 4 * B, "need 4 disjoint slot segments"
    tbl_a = jnp.asarray(rng.integers(0, 100, (T, K)), jnp.int32)
    tbl_b = jnp.asarray(rng.integers(0, 100, (T, 2)), jnp.int32)  # planes
    ring = jnp.asarray(rng.integers(0, T, (T,)), jnp.int32)
    # pairwise-DISJOINT row sets between different ops (the kernel's
    # guards make record kinds disjoint per row — only same-op duplicates
    # and commutative ops may collide, which is what the mega-pass's
    # chunk-major ordering relies on); adds/maxes carry duplicates inside
    # their own slot vector (commutative)
    perm = rng.permutation(T)
    slots_a = jnp.asarray(perm[:B], jnp.int32)
    slots_b = jnp.asarray(perm[B : 2 * B], jnp.int32)
    slots_c = jnp.asarray(rng.choice(perm[2 * B : 3 * B], B), jnp.int32)
    slots_d = jnp.asarray(rng.choice(perm[3 * B : 4 * B], B), jnp.int32)
    act_a = jnp.asarray(rng.random(B) < 0.7)
    act_b = jnp.asarray(rng.random(B) < 0.6)
    act_c = jnp.asarray(rng.random(B) < 0.5)
    act_d = jnp.asarray(rng.random(B) < 0.5)
    vals = jnp.asarray(rng.integers(0, 1000, (B, K)), jnp.int32)
    vals2 = jnp.asarray(rng.integers(0, 1000, (B, 2)), jnp.int32)
    mask = jnp.asarray(rng.random((B, K)) < 0.4)
    lvals = jnp.asarray(rng.integers(0, 9, (B,)), jnp.int32)

    def ops():
        return [
            pops.TableOp(0, "add", slots_c, act_c, vals, mask),
            pops.TableOp(0, "set", slots_a, act_a, vals, mask),
            pops.TableOp(0, "max", slots_d, act_d, vals),
            pops.TableOp(0, "set", slots_b, act_b, vals),
            pops.TableOp(1, "set", slots_a, act_a, vals2),
            pops.TableOp(2, "set", slots_b, act_b, lvals),
            pops.TableOp(2, "add", slots_c, act_c, lvals),
        ]

    with pops.forced("xla"):
        ref = pops.fused_table_commit([tbl_a, tbl_b, ring], ops())
    with pops.forced("pallas"):
        got = pops.fused_table_commit([tbl_a, tbl_b, ring], ops())
    for name, r, g in zip(("rows", "planes", "lanes"), ref, got):
        check(f"fused commit {name}", r, g)


def check_fused_gather(rng, T, B):
    """Phase-B/C mega-gather parity: fused_gather_rows (one pallas read
    pass) vs the XLA concat-gather fallback, over every table normal
    form the kernel feeds it — 2D i32/i64/f32/i8, 1D i32/i64/f32 — with
    duplicate indices in the slot vectors (reads commute, so duplicates
    are legal everywhere, unlike the commit pass)."""
    K = 16
    tbl_i32 = jnp.asarray(rng.integers(-(2**31), 2**31, (T, K)), jnp.int32)
    tbl_i64 = jnp.asarray(
        rng.integers(-(2**62), 2**62, (T, K), dtype=np.int64)
    )
    tbl_f32 = jax.lax.bitcast_convert_type(
        jnp.asarray(rng.integers(-(2**31), 2**31, (T, K)), jnp.int32),
        jnp.float32,
    )
    tbl_i8 = jnp.asarray(rng.integers(-128, 128, (T, K)), jnp.int8)
    t1_i32 = jnp.asarray(rng.integers(-(2**31), 2**31, (T,)), jnp.int32)
    t1_i64 = jnp.asarray(
        rng.integers(-(2**62), 2**62, (T,), dtype=np.int64)
    )
    t1_f32 = jax.lax.bitcast_convert_type(
        jnp.asarray(rng.integers(-(2**31), 2**31, (T,)), jnp.int32),
        jnp.float32,
    )
    tables = [tbl_i32, tbl_i64, tbl_f32, tbl_i8, t1_i32, t1_i64, t1_f32]
    # duplicate-heavy slots (rng.choice with replacement) + two ops sharing
    # one table, mirroring the kernel's ei table read at 3 roles
    slot_sets = [
        jnp.asarray(rng.choice(T, B), jnp.int32) for _ in range(9)
    ]
    ops = [pops.GatherOp(0, slot_sets[0]), pops.GatherOp(0, slot_sets[1]),
           pops.GatherOp(1, slot_sets[2]), pops.GatherOp(2, slot_sets[3]),
           pops.GatherOp(3, slot_sets[4]), pops.GatherOp(4, slot_sets[5]),
           pops.GatherOp(5, slot_sets[6]), pops.GatherOp(6, slot_sets[7])]
    with pops.forced("xla"):
        ref = pops.fused_gather_rows(tables, ops)
    with pops.forced("pallas"):
        got = pops.fused_gather_rows(tables, ops)
    names = ("rows i32 a", "rows i32 b", "rows i64", "rows f32", "rows i8",
             "lane i32", "lane i64", "lane f32")
    for name, r, g in zip(names, ref, got):
        # f32 compares as bits: NaN payloads must round-trip too
        if r.dtype == jnp.float32:
            r = jax.lax.bitcast_convert_type(r, jnp.int32)
            g = jax.lax.bitcast_convert_type(g, jnp.int32)
        check(f"fused gather {name}", r, g)

    # duplicate-key first-occurrence mask path: slots produced by the
    # kernel's _first_per_key dedup (duplicate commands on one entity →
    # only the first masked row reads/commits); downstream consumes the
    # gathered rows under that mask
    from zeebe_tpu.tpu.kernel import _first_per_key

    keys = jnp.asarray(rng.choice(16, B).astype(np.int64))
    mask = jnp.asarray(rng.random(B) < 0.8)
    first = _first_per_key(keys, mask)
    slots = jnp.clip(keys.astype(jnp.int32), 0, T - 1)
    with pops.forced("xla"):
        (r,) = pops.fused_gather_rows([tbl_i64], [pops.GatherOp(0, slots)])
    with pops.forced("pallas"):
        (g,) = pops.fused_gather_rows([tbl_i64], [pops.GatherOp(0, slots)])
    check("fused gather first-occurrence rows",
          np.where(np.asarray(first)[:, None], np.asarray(r), -1),
          np.where(np.asarray(first)[:, None], np.asarray(g), -1))

    # emit-compact packed parity: batch.take_rows routes its two packed
    # matrices through the "emit" family — pallas vs XLA on the same
    # argsort permutation must be bit-identical per field
    from zeebe_tpu.tpu import batch as rb
    import dataclasses as _dc

    b = rb.empty(B, 4)
    b = _dc.replace(
        b,
        valid=jnp.asarray(rng.random(B) < 0.5),
        key=jnp.asarray(rng.integers(-(2**62), 2**62, (B,), dtype=np.int64)),
        elem=jnp.asarray(rng.integers(-(2**31), 2**31, (B,)), jnp.int32),
        v_num=jax.lax.bitcast_convert_type(
            jnp.asarray(rng.integers(-(2**31), 2**31, (B, 4)), jnp.int32),
            jnp.float32,
        ),
        v_vt=jnp.asarray(rng.integers(-128, 128, (B, 4)), jnp.int8),
        resp=jnp.asarray(rng.random(B) < 0.3),
    )
    with pops.forced("xla"):
        ref_b = rb.compact(b)
    with pops.forced("pallas"):
        got_b = rb.compact(b)
    for f in rb._FIELDS:
        r, g = getattr(ref_b, f), getattr(got_b, f)
        if r.dtype == jnp.float32:
            r = jax.lax.bitcast_convert_type(r, jnp.int32)
            g = jax.lax.bitcast_convert_type(g, jnp.int32)
        check(f"emit compact {f}", r, g)


def main():
    if jax.default_backend() != "tpu":
        # Mosaic is TPU-only: the CPU suite pins the XLA fallbacks (the
        # same code path), so off-chip this gate has nothing to compare
        # and fails; ci.sh calls it only where a TPU is attached.
        raise SystemExit("pallas_ops parity check needs a TPU backend")
    rng = np.random.default_rng(7)
    T, B = 1 << 13, 1 << 11
    check_fused_commit(np.random.default_rng(11), T, B)
    check_fused_gather(np.random.default_rng(13), T, B)

    # -- hashmap ops --------------------------------------------------------
    table = hashmap.make(T)
    keys = jnp.asarray(
        rng.choice(np.arange(1, 10 * T, 5, dtype=np.int64), B, replace=False)
    )
    vals = jnp.arange(B, dtype=jnp.int32)
    valid = jnp.asarray(rng.random(B) < 0.8)

    t_x, ok_x = hashmap.insert(table, keys, vals, valid)
    t_p, ok_p = pops.insert(table, keys, vals, valid)
    # bucket layout may differ on collisions (round-synchronous XLA claims
    # vs serial); the tables must be FUNCTIONALLY identical: same key set,
    # same key->val mapping under either lookup
    def keyset(tb):
        return np.sort(hashmap.host_keys(tb))

    check("insert key set", keyset(t_x), keyset(t_p))
    fx, sx = hashmap.lookup(t_x, keys, valid)
    fp, sp = hashmap.lookup(t_p, keys, valid)
    check("insert mapping found", fx, fp)
    check("insert mapping vals", np.where(np.asarray(fx), np.asarray(sx), -1),
          np.where(np.asarray(fp), np.asarray(sp), -1))
    check("insert ok", ok_x, ok_p)

    probe_keys = jnp.concatenate([keys[: B // 2], keys[: B // 2] + 1])
    pvalid = jnp.ones((B,), bool)
    # pallas lookup on the pallas-built table vs XLA lookup on it: the
    # lookup itself must agree with the XLA lookup on the SAME table
    f_x, s_x = hashmap.lookup(t_p, probe_keys, pvalid)
    f_p, s_p = pops.lookup(t_p, probe_keys, pvalid)
    check("lookup found", f_x, f_p)
    check("lookup slots", np.where(np.asarray(f_x), np.asarray(s_x), -1),
          np.where(np.asarray(f_p), np.asarray(s_p), -1))

    dvalid = jnp.asarray(rng.random(B) < 0.5) & valid
    d_x = hashmap.delete(t_x, keys, dvalid)
    d_p = pops.delete(t_p, keys, dvalid)
    check("delete key set", keyset(d_x), keyset(d_p))

    # lookups after deletes must still traverse tombstones identically
    f2_x, s2_x = hashmap.lookup(d_x, keys, valid)
    f2_p, s2_p = pops.lookup(d_p, keys, valid)
    check("post-delete found", f2_x, f2_p)

    # -- row updates --------------------------------------------------------
    K = 48
    tbl = jnp.asarray(rng.integers(0, 100, (T, K)), jnp.int32)
    slots = jnp.asarray(rng.integers(0, T, B), jnp.int32)
    active = jnp.asarray(rng.random(B) < 0.7)
    rows = jnp.asarray(rng.integers(0, 1000, (B, K)), jnp.int32)

    x = tbl.at[jnp.where(active, slots, T)].set(rows, mode="drop")
    p = pops.masked_row_update(tbl, slots, active, rows)
    # duplicate slots: XLA scatter order is unspecified; compare only rows
    # written by exactly one active record (the kernel's real usage has
    # mask-disjoint writers)
    slot_counts = np.bincount(np.asarray(slots)[np.asarray(active)], minlength=T)
    unique = slot_counts <= 1
    check("row update (unique rows)", np.asarray(x)[unique], np.asarray(p)[unique])

    lane_mask = jnp.asarray(rng.random((B, K)) < 0.3)
    old = tbl[jnp.clip(slots, 0, T - 1)]
    merged = jnp.where(lane_mask, rows, old)
    x2 = tbl.at[jnp.where(active, slots, T)].set(merged, mode="drop")
    p2 = pops.masked_row_update(tbl, slots, active, rows, lane_mask)
    check("masked row update (unique rows)", np.asarray(x2)[unique], np.asarray(p2)[unique])

    # -- lane updates -------------------------------------------------------
    t1 = jnp.asarray(rng.integers(0, 100, (T,)), jnp.int32)
    lvals = jnp.asarray(rng.integers(0, 9, (B,)), jnp.int32)
    x3 = t1.at[jnp.where(active, slots, T)].set(lvals, mode="drop")
    p3 = pops.masked_lane_update(t1, slots, active, lvals)
    check("lane update (unique)", np.asarray(x3)[unique], np.asarray(p3)[unique])

    x4 = t1.at[jnp.where(active, slots, T)].add(lvals, mode="drop")
    p4 = pops.masked_lane_accum(t1, slots, active, lvals)
    check("lane accum", x4, p4)  # addition commutes; duplicates compare too

    # -- cross-backend snapshot interchange (VERDICT round-3 #7) ------------
    # The failover path: a pallas-built table is snapshotted on the TPU
    # leader and restored on a CPU-mesh follower, where the XLA fallback
    # serves it. Bucket layout may differ between the builders, so the
    # restored table must be FUNCTIONALLY correct under the XLA ops:
    # every live key found with its value, absent keys not found, and
    # further inserts/deletes through the XLA path must keep working.
    # (the CPU backend registers beside the TPU in one process unless
    # JAX_PLATFORMS names the TPU alone; then this raises, by design)
    cpu = jax.devices("cpu")[0]
    snap = {
        # int64 on disk, as the engine's snapshot writes a map's keys
        "keys": hashmap.host_keys(t_p),
        "vals": np.asarray(t_p.vals),
    }
    with jax.default_device(cpu):
        t_cpu = hashmap.from_host(snap["keys"], snap["vals"])
        f_c, s_c = hashmap.lookup(t_cpu, jnp.asarray(np.asarray(probe_keys)),
                                  jnp.ones((B,), bool))
        check("tpu->cpu restore found", np.asarray(f_x), np.asarray(f_c))
        check("tpu->cpu restore vals",
              np.where(np.asarray(f_x), np.asarray(s_x), -1),
              np.where(np.asarray(f_c), np.asarray(s_c), -1))
        # the restored table keeps serving through the XLA path
        extra = jnp.asarray(np.arange(10 * T, 10 * T + 64, dtype=np.int64))
        t_cpu2, ok_c = hashmap.insert(
            t_cpu, extra, jnp.arange(64, dtype=jnp.int32),
            jnp.ones((64,), bool),
        )
        check("tpu->cpu post-restore insert ok", np.asarray(ok_c),
              np.ones((64,), bool))
        f_c2, s_c2 = hashmap.lookup(t_cpu2, extra, jnp.ones((64,), bool))
        check("tpu->cpu post-restore lookup", np.asarray(f_c2),
              np.ones((64,), bool))

    print("ALL OK")


if __name__ == "__main__":
    main()
