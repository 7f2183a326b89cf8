"""Span kernel (ops/phold_span.py, family 2 in ops/memberlist_span.py):
the memberlist span executable's share of its roofline.  The work is
memory-bound: the per-member state a micro-iteration needs, read once
and written once, over the executable's device time at the chip's HBM
bandwidth (benchmark/peaks.json).  Moves sim_s_per_wall_s."""

EXECUTABLE = "jit_run"  # the span loop's jit(run), ops/span_mesh.py

# Frozen from the SoA layout of ops/phold_span.py family 2 at its first
# PR (the yardstick must not move when the kernel does): per member, the
# UDP family's own state (the inbox, 64 entries of 48 bytes; the timer
# heap, 16 of 25; an 8-entry peer list; 856 bytes of scalars, drop
# causes and syscall slots), the member's tables (broadcast queue 16 x
# 29 bytes, relayed probes 4 x 25, suspicions 8 x 44, dead members
# 8 x 12), 506 bytes of SWIM scalars and scratch, one payload slot
# written (300 bytes) and four view entries touched (state, incarnation
# and order: 7 bytes each).  The rest of the (H, N) view is indexed,
# not scanned, and is not counted.
UDP_STATE = 64 * 48 + 16 * 25 + 4 * 8 + 856
MEMBER_TABLES = 16 * 29 + 4 * 25 + 8 * 44 + 8 * 12
SWIM_SCALARS, PAYLOAD_SLOT, VIEW_TOUCHED = 506, 300, 4 * 7


def span_bytes_per_micro_iter(n_members: int) -> int:
    per_member = (UDP_STATE + MEMBER_TABLES + SWIM_SCALARS + PAYLOAD_SLOT
                  + VIEW_TOUCHED)
    return 2 * n_members * per_member


def read(ctx):
    tr = ctx["trace"]
    iters = ctx["dispatch"]["phold"]["micro_iters"]
    if tr is None or ctx["peaks"] is None or iters <= 0 \
            or EXECUTABLE not in tr["modules_s"]:
        return None
    nbytes = span_bytes_per_micro_iter(
        ctx["config"]["params"]["n_members"]) * iters
    return 100.0 * nbytes / (tr["modules_s"][EXECUTABLE]
                             * ctx["peaks"]["hbm_bytes_per_s"])
