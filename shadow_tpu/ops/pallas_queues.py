"""Lane-parallel queue-scan kernels (ISSUE 16, second leg).

The kernel observatory attributes the span slope's low-occupancy tail
to two stages both families run every micro-iteration: the
token-bucket refill/conformance scan and the CoDel head
classification of the relay drains.  Both are pure elementwise
integer laws over the host lane — exactly the shape pallas maps to
the vector lanes — so they live here once as a lax reference (the
form both span kernels inline when `experimental.pallas_queue_kernels`
is off, and the byte-identity oracle for the tests) plus a pallas
twin built from the SAME law, `interpret=True` on the CPU backend so
tier-1 runs the real kernel path.

Both laws are integer-exact (no float ops — the CoDel control-time
Newton isqrt stays OUTSIDE these kernels, in the span modules), so
byte identity of all five sim channels holds with the kernels on; the
differential gate is tests/test_overlap.py, not an assumption.  The
REFILL_NS / CODEL_TARGET_NS / MTU constants stay defined in the span
modules (the pass-1 twin-constant contract extracts them there) and
are passed in at build time.
"""

from __future__ import annotations

import numpy as np

# CoDel's control-law interval (netplane codel_pop twin): the
# first_above arm horizon.  Same literal the span modules inline.
CODEL_INTERVAL_NS = 100_000_000


def bucket_step_ref(jnp, refill_ns, bal, nxt, refill, cap, unlimited,
                    size, now):
    """Token-bucket refill + conformance for every host lane at once
    (netplane token_bucket twin): lazy catch-up refill of `k` whole
    intervals, then the conformance check/debit.  Returns
    (bal3, nxt2, ok); the caller owns the masked writeback."""
    first = nxt == 0
    k = jnp.maximum(np.int64(0),
                    1 + (now - nxt) // np.int64(refill_ns))
    do_ref = ~first & (now >= nxt)
    bal2 = jnp.where(do_ref, jnp.minimum(cap, bal + k * refill),
                     bal)
    nxt2 = jnp.where(first, now + np.int64(refill_ns),
                     jnp.where(do_ref,
                               nxt + k * np.int64(refill_ns),
                               nxt))
    ok = unlimited | (size <= bal2)
    bal3 = jnp.where(~unlimited & ok, bal2 - size, bal2)
    return bal3, nxt2, ok


def codel_head_ref(jnp, target_ns, mtu, pop, none, now, enq,
                   bytes_after, first_above):
    """CoDel head classification of one relay dequeue per lane
    (netplane codel_pop dequeue_raw twin): sojourn vs target with the
    MTU standing-queue escape, first_above arming and the ok bit.
    `bytes_after` is the queue byte count AFTER the pop's decrement.
    Returns (quiet, above, arm, cok, fa_new); the drop chain / sniff
    unrolling stays in the span modules."""
    sojourn = now - enq
    quiet = pop & ((sojourn < target_ns) | (bytes_after <= mtu))
    above = pop & ~quiet
    arm = above & (first_above == 0)
    cok = above & ~arm & (now >= first_above)
    fa_new = jnp.where(
        quiet | none, 0,
        jnp.where(arm, now + np.int64(CODEL_INTERVAL_NS),
                  first_above))
    return quiet, above, arm, cok, fa_new


def _interpret(jax) -> bool:
    """Compiled pallas needs a real accelerator backend; the CPU
    backend runs the same kernel body through the pallas interpreter
    so tier-1 exercises the kernel path without TPU hardware.  On a
    TPU the kernels are compiled, never interpreted — and Mosaic
    refuses their int64 lanes today (tests/test_tpu_compile.py)."""
    return jax.default_backend() == "cpu"


def make_bucket_step(jax, jnp, H, refill_ns, use_pallas):
    """Build the bucket scan for an H-lane span kernel: the lax
    reference, or its pallas twin when `use_pallas`.  Signature of
    the returned fn: (bal, nxt, refill, cap, unlimited, size, now)
    -> (bal3, nxt2, ok) — i64 lanes except the bool unlimited/ok."""
    if not use_pallas:
        def step(bal, nxt, refill, cap, unlimited, size, now):
            return bucket_step_ref(jnp, refill_ns, bal, nxt, refill,
                                   cap, unlimited, size, now)
        return step

    from jax.experimental import pallas as pl

    def kernel(bal_ref, nxt_ref, refill_ref, cap_ref, unl_ref,
               size_ref, now_ref, bal_out, nxt_out, ok_out):
        bal3, nxt2, ok = bucket_step_ref(
            jnp, refill_ns, bal_ref[:], nxt_ref[:], refill_ref[:],
            cap_ref[:], unl_ref[:], size_ref[:], now_ref[:])
        bal_out[:] = bal3
        nxt_out[:] = nxt2
        ok_out[:] = ok

    call = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((H,), jnp.int64),
                   jax.ShapeDtypeStruct((H,), jnp.int64),
                   jax.ShapeDtypeStruct((H,), jnp.bool_)),
        interpret=_interpret(jax))

    def step(bal, nxt, refill, cap, unlimited, size, now):
        # The span kernels pass the span clock (and sometimes the
        # packet size) as scalars; pallas refs are lane-shaped.
        args = tuple(jnp.broadcast_to(jnp.asarray(a), (H,))
                     for a in (bal, nxt, refill, cap, unlimited,
                               size, now))
        return call(*args)
    return step


def make_codel_head(jax, jnp, H, target_ns, mtu, use_pallas):
    """Build the CoDel head classification for an H-lane span kernel:
    the lax reference, or its pallas twin when `use_pallas`.
    Signature of the returned fn: (pop, none, now, enq, bytes_after,
    first_above) -> (quiet, above, arm, cok, fa_new)."""
    if not use_pallas:
        def head(pop, none, now, enq, bytes_after, first_above):
            return codel_head_ref(jnp, target_ns, mtu, pop, none,
                                  now, enq, bytes_after, first_above)
        return head

    from jax.experimental import pallas as pl

    def kernel(pop_ref, none_ref, now_ref, enq_ref, bytes_ref,
               fa_ref, quiet_out, above_out, arm_out, cok_out,
               fa_out):
        quiet, above, arm, cok, fa_new = codel_head_ref(
            jnp, target_ns, mtu, pop_ref[:], none_ref[:], now_ref[:],
            enq_ref[:], bytes_ref[:], fa_ref[:])
        quiet_out[:] = quiet
        above_out[:] = above
        arm_out[:] = arm
        cok_out[:] = cok
        fa_out[:] = fa_new

    call = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((H,), jnp.bool_),
                   jax.ShapeDtypeStruct((H,), jnp.bool_),
                   jax.ShapeDtypeStruct((H,), jnp.bool_),
                   jax.ShapeDtypeStruct((H,), jnp.bool_),
                   jax.ShapeDtypeStruct((H,), jnp.int64)),
        interpret=_interpret(jax))

    def head(pop, none, now, enq, bytes_after, first_above):
        args = tuple(jnp.broadcast_to(jnp.asarray(a), (H,))
                     for a in (pop, none, now, enq, bytes_after,
                               first_above))
        return call(*args)
    return head
