"""Device twin of the engine's memberlist member: family 2 of the UDP
span kernel (ops/phold_span.py).

netplane.cpp APP_MEMBERLIST runs one member of a memberlist LAN pool
(HashiCorp memberlist's DefaultLANConfig: SWIM with Lifeguard) over one
UDP socket.  Its wake runs the due protocol timers, then reads every
queued datagram, then arms one TK_APP at its earliest deadline and
parks on readable.  Here each member is one lane, and that wake is a
small program (`ml_pc`) whose phases run in order within a micro-op
until the lane sends a datagram: a send hands the lane to the inet-out
relay drain, which returns it to the program (C_M_STEP), exactly where
the engine's sendto drains the relay synchronously.  Per lane the
order of every effect (state, event-seq and packet-seq draws, sends) is
the engine's, so traces and state are byte-identical.

Every member's view of every member is dense on the device: state
(`ml_vstate`, u8), incarnation (`ml_vinc`, u32) and probe order
(`ml_order`, u16), each (H, N).  The rare entries live in per-member
tables with fixed capacities: the broadcast queue (`ml_bq_*`), the
relayed probes (`ml_rl_*`), the suspicion timers (`ml_su_*`) and the
dead members (`ml_dd_*`); an overflow aborts the span (AB_STRUCT) and
the engine runs those rounds.  A datagram's parts travel in a payload
store keyed by (sender, packet number % Q): the receiver checks the
stored packet number and aborts on a mismatch, and the span aborts if
a datagram still in flight lost its slot.
"""

from __future__ import annotations

import numpy as np

from shadow_tpu.core.rng import threefry2x32_jax

# netplane.cpp ML_* / MLM_* / MLC_* twins (analysis pass 1)
ML_ALIVE = 0
ML_SUSPECT = 1
ML_DEAD = 2
ML_REAPED = 3
MLM_PING = 1
MLM_PINGREQ = 2
MLM_ACK = 3
MLM_NACK = 4
MLM_SUSPECT = 5
MLM_DEAD = 6
MLM_ALIVE = 7
ML_NEVER = 1 << 62
ML_PROBE_IV = 1000000000
ML_PROBE_TO = 500000000
ML_GOSSIP_IV = 200000000
ML_G2DEAD = 30000000000
ML_INDIRECT = 3
ML_GOSSIP_NODES = 3
ML_RETX_MULT = 4
ML_SUSP_MULT = 4
ML_SUSP_MAX_MULT = 6
ML_SUSP_K = 2
ML_AWARE_MAX = 8
ML_UDP_BUF = 1400
ML_DRAW_STAGGER = 0
ML_DRAW_SHUFFLE = 1
ML_DRAW_PICK = 2
MLC_PING = 0
MLC_ACK = 1
MLC_PINGREQ = 2
MLC_NACK = 3
MLC_SUSPECT = 4
MLC_DEAD = 5
MLC_ALIVE = 6
MLC_UPD_SENT = 7
MLC_UPD_DROPPED = 8
MLC_N = 9
MLC_NAMES = ("pings", "acks", "ping_reqs", "nacks", "suspect_applied",
             "dead_applied", "alive_applied", "updates_sent",
             "updates_dropped")

# the wake's program counter
PC_ENTRY, PC_TIMER, PC_WALK, PC_GOSSIP, PC_PREQ = 0, 1, 2, 3, 4
PC_RECV, PC_PART, PC_DONE = 5, 6, 7

# a message's length less its msgpack SeqNo / Incarnation, by type
_BASE = (0, 68, 94, 17, 8, 38, 38, 61)

# The family's codec columns (netplane.cpp ml_export / ml_import and
# span_export_ml_view / span_import_ml_view), as the engine packs them.
# Per member, static (the member and its protocol key) and carried:
ML_STATIC_SCALARS = (("ml_self", np.int32), ("ml_key_lo", np.uint32),
                     ("ml_key_hi", np.uint32))
ML_SCALARS = (
    ("ml_n", np.int32), ("ml_score", np.int32),
    ("ml_pidx", np.int32), ("ml_inc", np.uint32), ("ml_seq", np.uint32),
    ("ml_ctr_shuf", np.uint32), ("ml_ctr_pick", np.uint32),
    ("ml_probe_next", np.int64), ("ml_gossip_next", np.int64),
    ("ml_armed", np.int64), ("ml_tick", np.uint8),
    ("ml_p_active", np.uint8), ("ml_p_target", np.int32),
    ("ml_p_exp", np.int32), ("ml_p_nacks", np.int32),
    ("ml_p_seq", np.uint32), ("ml_p_inc", np.uint32),
    ("ml_p_to", np.int64), ("ml_p_dl", np.int64), ("ml_bq_id", np.int64))
# Per member, one row of a table: (key, dtype, index of its cap in caps)
ML_TABLES = (
    ("ml_bq_node", np.int32, 0), ("ml_bq_from", np.int32, 0),
    ("ml_bq_tx", np.int32, 0), ("ml_bq_len", np.int32, 0),
    ("ml_bq_kind", np.uint8, 0), ("ml_bq_inc", np.uint32, 0),
    ("ml_bq_bid", np.int64, 0), ("ml_rl_valid", np.int32, 1),
    ("ml_rl_seq", np.uint32, 1), ("ml_rl_oseq", np.uint32, 1),
    ("ml_rl_from", np.int32, 1), ("ml_rl_nack", np.uint8, 1),
    ("ml_rl_dl", np.int64, 1), ("ml_su_node", np.int32, 2),
    ("ml_su_from", np.int32, 2), ("ml_su_n0", np.int32, 2),
    ("ml_su_k", np.int32, 2), ("ml_su_c", np.int32, 2),
    ("ml_su_conf0", np.int32, 2), ("ml_su_conf1", np.int32, 2),
    ("ml_su_start", np.int64, 2), ("ml_su_dl", np.int64, 2),
    ("ml_dd_node", np.int32, 3), ("ml_dd_t", np.int64, 3))
# The dense views, (H, N) each
ML_VIEW = (("ml_vstate", np.uint8), ("ml_vinc", np.uint32),
           ("ml_order", np.uint16))

# Residency classes (ops/phold_span.py RESIDENT_*): static config,
# carried state, and the wake's scratch, which is zero at every clean
# span boundary.
RESIDENT_STATIC = frozenset({
    "ml_self", "ml_ips", "ml_sus_min", "ml_sus_conf", "ml_retx",
    "ml_key_lo", "ml_key_hi"})
RESIDENT_CARRIED = frozenset({
    "ml_n", "ml_score", "ml_pidx", "ml_inc", "ml_seq", "ml_ctr_shuf",
    "ml_ctr_pick", "ml_probe_next", "ml_gossip_next", "ml_armed",
    "ml_tick", "ml_p_active", "ml_p_target", "ml_p_exp", "ml_p_nacks",
    "ml_p_seq", "ml_p_inc", "ml_p_to", "ml_p_dl", "ml_bq_id", "ml_cnt",
    "ml_bq_node", "ml_bq_from", "ml_bq_tx", "ml_bq_len", "ml_bq_kind",
    "ml_bq_inc", "ml_bq_bid", "ml_rl_valid", "ml_rl_seq", "ml_rl_oseq",
    "ml_rl_from", "ml_rl_nack", "ml_rl_dl", "ml_su_node", "ml_su_from",
    "ml_su_n0", "ml_su_k", "ml_su_c", "ml_su_conf0", "ml_su_conf1",
    "ml_su_start", "ml_su_dl", "ml_dd_node", "ml_dd_t", "ml_vstate",
    "ml_vinc", "ml_order", "ml_pay", "ml_pay_n", "ml_pay_tag"})
RESIDENT_DERIVED = frozenset({
    "ml_pc", "ml_ret", "ml_sub", "ml_npk", "ml_pk", "ml_cur",
    "ml_cur_n", "ml_ci", "ml_from"})


def ml_derived(xp, H: int, caps) -> dict:
    """The wake's scratch at a clean span boundary."""
    NP = caps[5]
    z = xp.zeros(H, xp.int32)
    return {"ml_pc": z, "ml_ret": z, "ml_sub": z, "ml_npk": z,
            "ml_pk": xp.zeros((H, ML_INDIRECT), xp.int32),
            "ml_cur": xp.zeros((H, NP, 4), xp.int32), "ml_cur_n": z,
            "ml_ci": z, "ml_from": z}


class MlCodec:
    """Engine bytes <-> the family's numpy state, for H hosts and the
    runner's caps (BQ, RL, SU, DD, Q, NP).  The wake's scratch
    (ml_derived) is the caller's to add."""

    def __init__(self, H: int, caps):
        self.H = H
        self.caps = tuple(caps)

    def _to_arrays(self, d: dict) -> dict:
        H = self.H
        BQ, RL, SU, DD, Q, NP = self.caps

        def f(k, dt, shape):
            a = np.frombuffer(d[k], dtype=dt)
            return a.reshape(shape).copy()

        st = {}
        for k, dt in ML_STATIC_SCALARS:
            st[k] = f(k, dt, (H,))
        for k, dt in ML_SCALARS:
            st[k] = f(k, dt, (H,))
        for k, dt, which in ML_TABLES:
            st[k] = f(k, dt, (H, self.caps[which]))
        st["ml_ips"] = f("ml_ips", np.uint32, (-1,))
        N = st["ml_ips"].shape[0]
        st["ml_sus_min"] = f("ml_sus_min", np.int64, (N + 1,))
        st["ml_sus_conf"] = f("ml_sus_conf", np.int64, ((N + 1) * 3,))
        st["ml_retx"] = f("ml_retx", np.int32, (N + 1,))
        st["ml_cnt"] = f("ml_cnt", np.int64, (H, MLC_N))
        st["ml_pay"] = f("ml_pay", np.int32, (H, Q, NP, 4))
        st["ml_pay_n"] = f("ml_pay_n", np.int32, (H, Q))
        st["ml_pay_tag"] = f("ml_pay_tag", np.int64, (H, Q))
        return st

    def _from_arrays(self, st: dict) -> dict:
        out = {}

        def b(k, dt):
            return np.ascontiguousarray(
                np.asarray(st[k]).astype(dt)).tobytes()

        for k, dt in ML_SCALARS:
            out[k] = b(k, dt)
        for k, dt, _which in ML_TABLES:
            out[k] = b(k, dt)
        out["ml_cnt"] = b("ml_cnt", np.int64)
        out["ml_pay"] = b("ml_pay", np.int32)
        out["ml_pay_n"] = b("ml_pay_n", np.int32)
        out["ml_pay_tag"] = b("ml_pay_tag", np.int64)
        out["ml_caps"] = np.asarray(self.caps, np.int64).tobytes()
        return out

    def _view_to_arrays(self, d: dict) -> dict:
        st = {}
        for k, dt in ML_VIEW:
            a = np.frombuffer(d[k], dtype=dt)
            st[k] = a.reshape(self.H, -1).copy()
        return st

    def _view_from_arrays(self, st: dict) -> dict:
        out = {}
        for k, _dt in ML_VIEW:
            out[k] = np.ascontiguousarray(np.asarray(st[k])).tobytes()
        return out


def widen_view(jnp, st: dict) -> dict:
    """The view's u8 states and u16 order as int32, for a span."""
    st = dict(st)
    st["ml_vstate"] = st["ml_vstate"].astype(jnp.int32)
    st["ml_order"] = st["ml_order"].astype(jnp.int32)
    return st


def narrow_view(jnp, st: dict) -> dict:
    """widen_view's inverse, at the span's end."""
    st = dict(st)
    st["ml_vstate"] = st["ml_vstate"].astype(jnp.uint8)
    st["ml_order"] = st["ml_order"].astype(jnp.uint16)
    return st


def build_ml_step(jax, jnp, H: int, N: int, caps, hx):
    """The C_M_STEP micro-op of family 2, and the span-end payload
    check.  `hx` carries the span kernel's helpers (mark_abort,
    draw_seq, th_push, set_status, ks_count, stage, mrows) and
    constants (hidx, S, AB_STRUCT, TK_APP, S_READABLE, S_WRITABLE,
    ASYS_SENDTO, ASYS_RECVFROM, KS_PROBE, KS_GOSSIP, KS_MERGE, PKF,
    C_IDLE, C_R1, C_M_STEP)."""
    BQ, RL, SU, DD, Q, NP = caps
    # Ring and payload-slot indices are masks, never `%`: a TPU has no
    # integer divider, and every 64-bit remainder expands into
    # thousands of HLO ops.
    for cap in (Q, hx.R, hx.S, hx.C):
        assert cap & (cap - 1) == 0, "capacities must be powers of two"
    i32, i64, u32 = jnp.int32, jnp.int64, jnp.uint32
    hidx = hx.hidx

    def slot_of(pseq):
        return (pseq & (Q - 1)).astype(i32)
    mrows = hx.mrows
    base = jnp.asarray(_BASE, i32)
    NEVER = np.int64(ML_NEVER)

    def upd(st, **kv):
        st = dict(st)
        st.update(kv)
        return st

    def put(st, key, mask, val):
        return upd(st, **{key: jnp.where(mask, val, st[key])})

    def uint_len(v):
        v = v.astype(u32)
        return jnp.where(v < 128, 1, jnp.where(v < 256, 2, jnp.where(
            v < 65536, 3, 5))).astype(i32)

    def msg_len(kind, a):
        return base[kind] + uint_len(a)

    def draw(st, kind, ctr):
        c0 = st["ml_self"].astype(u32) | u32(kind << 20)
        x0, _ = threefry2x32_jax(st["ml_key_lo"], st["ml_key_hi"], c0,
                                 ctr.astype(u32))
        return x0

    def node_idx(j):
        return jnp.clip(j, 0, N - 1).astype(i32)

    def vget(st, key, j):
        return st[key][hidx, node_idx(j)]

    def vset(st, key, mask, j, v):
        a = st[key]
        return upd(st, **{key: a.at[mrows(mask), node_idx(j)].set(
            jnp.asarray(v).astype(a.dtype), mode="drop")})

    def cnt(st, mask, which, by=1):
        c = st["ml_cnt"]
        add = jnp.where(mask, by, 0).astype(i64)
        return upd(st, ml_cnt=c.at[:, which].add(add))

    def col_of(match):
        """one-hot column of the first True per row"""
        first = jnp.argmax(match, axis=1)
        return jnp.arange(match.shape[1])[None, :] == first[:, None]

    def tset(st, key, oh, val):
        a = st[key]
        return upd(st, **{key: jnp.where(
            oh, jnp.asarray(val).astype(a.dtype)[:, None], a)})

    def tget(st, key, oh):
        a = st[key]
        return jnp.where(oh, a, jnp.zeros((), a.dtype)).sum(
            axis=1).astype(a.dtype)

    def insert(st, pfx, valid_key, free_val, mask, vals: dict):
        """first free slot of a per-member table; abort when full"""
        free = st[valid_key] == free_val
        full = mask & ~free.any(axis=1)
        oh = col_of(free) & (mask & ~full)[:, None]
        for k, v in vals.items():
            st = tset(st, pfx + k, oh, v)
        return hx.mark_abort(st, full.any(), hx.AB_STRUCT)

    # -- broadcast queue ------------------------------------------------

    def broadcast(st, mask, kind, inc, node, frm):
        node_t = st["ml_bq_node"]
        same = mask[:, None] & (node_t == node[:, None]) & (node_t >= 0)
        st = upd(st, ml_bq_node=jnp.where(same, -1, node_t))
        kind = jnp.broadcast_to(jnp.asarray(kind, i32), (H,))
        st = insert(st, "ml_bq_", "ml_bq_node", -1, mask, {
            "node": node, "kind": kind, "inc": inc, "from": frm,
            "tx": jnp.zeros(H, i32), "bid": st["ml_bq_id"],
            "len": msg_len(kind, inc)})
        return put(st, "ml_bq_id", mask, st["ml_bq_id"] + 1)

    def get(st, mask, limit, parts, n):
        """GetBroadcasts(2, limit) for the masked lanes: appends parts
        at n; returns (st, parts, n, used)."""
        # Inner loops carry only their own values: a loop that carried
        # the whole state would copy the (H, N) view through it.
        def body(_i, c):
            parts, n, used, taken = c
            node, tx = st["ml_bq_node"], st["ml_bq_tx"]
            ln, bid = st["ml_bq_len"], st["ml_bq_bid"]
            free = limit - used - 2
            cand = ((mask & (free > 0))[:, None] & (node >= 0) & ~taken
                    & (ln.astype(i64) <= free[:, None]))
            tmin = jnp.where(cand, tx, np.int32(1 << 30)).min(axis=1)
            c2 = cand & (tx == tmin[:, None])
            lmax = jnp.where(c2, ln, -1).max(axis=1)
            c3 = c2 & (ln == lmax[:, None])
            bmax = jnp.where(c3, bid, np.int64(-1)).max(axis=1)
            pick = c3 & (bid == bmax[:, None])
            got = pick.any(axis=1)
            taken = taken | pick
            used = used + jnp.where(got, 2 + lmax, 0).astype(i64)
            part = jnp.stack([tget(st, "ml_bq_kind", pick).astype(i32),
                              tget(st, "ml_bq_inc", pick).astype(i32),
                              tget(st, "ml_bq_node", pick),
                              tget(st, "ml_bq_from", pick)], axis=1)
            at = (jnp.arange(NP)[None, :] == n[:, None]) & got[:, None]
            parts = jnp.where(at[:, :, None], part[:, None, :], parts)
            return parts, n + got.astype(i32), used, taken

        used0 = jnp.zeros(H, i64)
        taken0 = jnp.zeros((H, BQ), bool)
        parts, n, used, taken = jax.lax.fori_loop(
            0, min(BQ, NP - 1), body, (parts, n, used0, taken0))
        lim = st["ml_retx"][st["ml_n"]]
        tx = st["ml_bq_tx"] + taken.astype(i32)
        st = cnt(st, mask, MLC_UPD_SENT, taken.sum(axis=1))
        drop = (mask[:, None] & (st["ml_bq_node"] >= 0)
                & (tx >= lim[:, None]))
        st = cnt(st, mask, MLC_UPD_DROPPED, drop.sum(axis=1))
        st = upd(st, ml_bq_tx=tx,
                 ml_bq_node=jnp.where(drop, -1, st["ml_bq_node"]))
        return st, parts, n, used

    # -- membership -----------------------------------------------------

    def aware(st, mask, delta):
        v = jnp.clip(st["ml_score"] + delta, 0, ML_AWARE_MAX - 1)
        return put(st, "ml_score", mask, v)

    def refute(st, mask, accused):
        accused = accused.astype(u32)
        inc = st["ml_inc"] + u32(1)
        inc = jnp.where(accused >= inc, accused + u32(1), inc)
        st = put(st, "ml_inc", mask, inc)
        st = vset(st, "ml_vinc", mask, st["ml_self"], inc)
        st = aware(st, mask, 1)
        return broadcast(st, mask, MLM_ALIVE, inc, st["ml_self"],
                         st["ml_self"])

    def del_table(st, key, mask, node):
        t = st[key]
        hit = mask[:, None] & (t == node[:, None]) & (t >= 0)
        return upd(st, **{key: jnp.where(hit, -1, t)})

    def suspect(st, mask, inc, node, frm, now):
        inc = inc.astype(u32)
        s = vget(st, "ml_vstate", node)
        ok = mask & (s != ML_REAPED) & (inc >= vget(st, "ml_vinc", node))
        match = (st["ml_su_node"] == node[:, None]) & \
            (st["ml_su_node"] >= 0)
        has = ok & match.any(axis=1)
        oh = col_of(match) & has[:, None]
        c = tget(st, "ml_su_c", oh)
        f0 = tget(st, "ml_su_from", oh)
        cf0 = tget(st, "ml_su_conf0", oh)
        cf1 = tget(st, "ml_su_conf1", oh)
        acc = (has & (c < tget(st, "ml_su_k", oh)) & (frm != f0)
               & ~((c > 0) & (frm == cf0)) & ~((c > 1) & (frm == cf1)))
        ao = oh & acc[:, None]
        cn = c + 1
        st = tset(st, "ml_su_conf0", ao, jnp.where(c == 0, frm, cf0))
        st = tset(st, "ml_su_conf1", ao, jnp.where(c == 1, frm, cf1))
        st = tset(st, "ml_su_c", ao, cn)
        n0 = tget(st, "ml_su_n0", oh)
        dl = (tget(st, "ml_su_start", oh)
              + st["ml_sus_conf"][jnp.clip(n0 * 3 + cn, 0, 3 * N + 2)])
        st = tset(st, "ml_su_dl", ao, jnp.where(dl > now, dl, now + 1))
        st = broadcast(st, acc, MLM_SUSPECT, inc, node, frm)
        new = ok & ~has & (s == ML_ALIVE)
        me = new & (node == st["ml_self"])
        st = refute(st, me, inc)
        oth = new & ~me
        st = broadcast(st, oth, MLM_SUSPECT, inc, node, frm)
        st = cnt(st, oth, MLC_SUSPECT)
        st = vset(st, "ml_vinc", oth, node, inc)
        st = vset(st, "ml_vstate", oth, node, ML_SUSPECT)
        n = st["ml_n"]
        k = jnp.where(n - 2 < ML_SUSP_K, 0, ML_SUSP_K).astype(i32)
        mn = st["ml_sus_min"][n]
        return insert(st, "ml_su_", "ml_su_node", -1, oth, {
            "node": node, "from": frm, "n0": n, "k": k,
            "c": jnp.zeros(H, i32), "conf0": jnp.zeros(H, i32),
            "conf1": jnp.zeros(H, i32), "start": now,
            "dl": now + jnp.where(k < 1, mn, ML_SUSP_MAX_MULT * mn)})

    def dead(st, mask, inc, node, frm, now):
        inc = inc.astype(u32)
        s = vget(st, "ml_vstate", node)
        ok = mask & (s != ML_REAPED) & (inc >= vget(st, "ml_vinc", node))
        st = del_table(st, "ml_su_node", ok, node)
        ok = ok & (s != ML_DEAD)
        me = ok & (node == st["ml_self"])
        st = refute(st, me, inc)
        oth = ok & ~me
        st = broadcast(st, oth, MLM_DEAD, inc, node, frm)
        st = cnt(st, oth, MLC_DEAD)
        st = vset(st, "ml_vinc", oth, node, inc)
        st = vset(st, "ml_vstate", oth, node, ML_DEAD)
        return insert(st, "ml_dd_", "ml_dd_node", -1, oth,
                      {"node": node, "t": now})

    def alive(st, mask, inc, node):
        inc = inc.astype(u32)
        ok = mask & (inc > vget(st, "ml_vinc", node))
        me = ok & (node == st["ml_self"])
        st = refute(st, me, inc)
        oth = ok & ~me
        st = del_table(st, "ml_su_node", oth, node)
        st = broadcast(st, oth, MLM_ALIVE, inc, node, node)
        st = cnt(st, oth, MLC_ALIVE)
        st = vset(st, "ml_vinc", oth, node, inc)
        s = vget(st, "ml_vstate", node)
        st = del_table(st, "ml_dd_node", oth & (s == ML_DEAD), node)
        st = put(st, "ml_n", oth & (s == ML_REAPED), st["ml_n"] + 1)
        return vset(st, "ml_vstate", oth, node, ML_ALIVE)

    def pick(st, mask, k, excl, alive_only, now):
        """kRandomNodes into ml_pk / ml_npk"""
        def cond(c):
            got, _out, tries, _ctr = c
            return (mask & (got < k) & (tries < 3 * N)).any()

        def body(c):
            got, out, tries, ctr = c
            act = mask & (got < k) & (tries < 3 * N)
            j = (draw(st, ML_DRAW_PICK, ctr) % u32(N)).astype(i32)
            s = vget(st, "ml_vstate", j)
            if alive_only:
                bad = s != ML_ALIVE
            else:
                t = jnp.where(st["ml_dd_node"] == j[:, None],
                              st["ml_dd_t"], NEVER).min(axis=1)
                bad = (s == ML_REAPED) | ((s == ML_DEAD)
                                          & (now - t > ML_G2DEAD))
            bad = bad | (j == st["ml_self"]) | (j == excl)
            slot = jnp.arange(ML_INDIRECT)[None, :]
            dup = ((out == j[:, None]) & (slot < got[:, None])).any(axis=1)
            add = act & ~bad & ~dup
            out = jnp.where((slot == got[:, None]) & add[:, None],
                            j[:, None], out)
            return (got + add.astype(i32), out, tries + act.astype(i32),
                    jnp.where(act, ctr + u32(1), ctr))

        z = jnp.zeros(H, i32)
        got, out, _t, ctr = jax.lax.while_loop(
            cond, body, (z, jnp.zeros((H, ML_INDIRECT), i32), z,
                         st["ml_ctr_pick"]))
        st = upd(st, ml_ctr_pick=ctr)
        st = put(st, "ml_npk", mask, got)
        st = upd(st, ml_pk=jnp.where(mask[:, None], out, st["ml_pk"]))
        return put(st, "ml_sub", mask, 0)

    # -- probing --------------------------------------------------------

    def reset(st, mask, now):
        """the walk wrapped: reap, then reshuffle the whole order"""
        dn, dt = st["ml_dd_node"], st["ml_dd_t"]
        reap = mask[:, None] & (dn >= 0) & (now[:, None] - dt > ML_G2DEAD)
        for q in range(DD):
            st = vset(st, "ml_vstate", reap[:, q], dn[:, q], ML_REAPED)
        st = put(st, "ml_n", mask,
                 st["ml_n"] - reap.sum(axis=1).astype(i32))
        st = upd(st, ml_dd_node=jnp.where(reap, -1, dn))

        def swap(q, c):
            order, ctr = c
            i = N - 1 - q
            j = (draw(st, ML_DRAW_SHUFFLE, ctr)
                 % (i + 1).astype(u32)).astype(i32)
            a, b = order[hidx, i], order[hidx, j]
            rows = mrows(mask)
            order = order.at[rows, i].set(b, mode="drop")
            order = order.at[rows, j].set(a, mode="drop")
            return order, ctr + u32(1)

        # no lax.cond around it (see op_ml): no lane wraps, no trips
        order, ctr = jax.lax.fori_loop(
            0, jnp.where(mask.any(), N - 1, 0), swap,
            (st["ml_order"], st["ml_ctr_shuf"]))
        st = upd(st, ml_order=order,
                 ml_ctr_shuf=jnp.where(mask, ctr, st["ml_ctr_shuf"]))
        return put(st, "ml_pidx", mask, 0)

    def walk(st, mask, now):
        """memberlist.probe()'s walk: (st, found, target).  A lane wraps
        at most once a walk (after a wrap, N skips end it), so the walk
        runs read-only to a target or the end, the lanes at the end
        reset, and a second pass walks from the top."""
        def scan(pidx, checks, act):
            def cond(c):
                return c[2].any()

            def body(c):
                pidx, checks, act, found, tgt = c
                act = act & (checks < N) & (pidx < N)
                j = st["ml_order"][hidx, jnp.clip(pidx, 0, N - 1)]
                j = j.astype(i32)
                s = vget(st, "ml_vstate", j)
                skip = (j == st["ml_self"]) | (s == ML_DEAD) | \
                    (s == ML_REAPED)
                hit = act & ~skip
                return (jnp.where(act, pidx + 1, pidx),
                        checks + (act & skip).astype(i32), act & ~hit,
                        found | hit, jnp.where(hit, j, tgt))

            z = jnp.zeros(H, i32)
            return jax.lax.while_loop(
                cond, body, (pidx, checks, act, jnp.zeros(H, bool), z))

        z = jnp.zeros(H, i32)
        pidx, checks, _a, found, tgt = scan(st["ml_pidx"], z, mask)
        wrap = mask & ~found & (checks < N) & (pidx >= N)
        st = put(st, "ml_pidx", mask, pidx)
        st = reset(st, wrap, now)
        pidx2, _c, _a, found2, tgt2 = scan(st["ml_pidx"], checks + 1, wrap)
        st = put(st, "ml_pidx", wrap, pidx2)
        return st, found | found2, jnp.where(found2, tgt2, tgt)

    def probe_end(st, mask, delta, ret):
        st = put(st, "ml_p_active", mask, 0)
        st = aware(st, mask, delta)
        go = mask & (st["ml_tick"] == 1)
        st = put(st, "ml_tick", go, 0)
        st = put(st, "ml_ret", go, ret)
        return put(st, "ml_pc", go, PC_WALK)

    # -- the send request each lane may raise in one micro-op -----------

    def request(rq, mask, dst, parts, n, size, piggy, mlen):
        keep = ~mask
        return {
            "want": rq["want"] | mask,
            "dst": jnp.where(keep, rq["dst"], dst),
            "parts": jnp.where(keep[:, None, None], rq["parts"], parts),
            "n": jnp.where(keep, rq["n"], n),
            "size": jnp.where(keep, rq["size"], size),
            "piggy": jnp.where(keep, rq["piggy"], piggy),
            "mlen": jnp.where(keep, rq["mlen"], mlen)}

    def one_part(kind, a, b, c):
        p = jnp.zeros((H, NP, 4), i32)
        v = jnp.stack([jnp.broadcast_to(jnp.asarray(kind, i32), (H,)),
                       a.astype(i32), b.astype(i32),
                       jnp.broadcast_to(jnp.asarray(c, i32), (H,))],
                      axis=1)
        return p.at[:, 0, :].set(v)

    def piggy_req(rq, mask, dst, kind, a, b, c):
        parts = one_part(kind, a, b, c)
        mlen = msg_len(jnp.asarray(kind, i32), a)
        return request(rq, mask, dst, parts, jnp.ones(H, i32),
                       mlen.astype(i64), True, mlen.astype(i64))

    def probe_node(st, rq, mask, j, now):
        seq = st["ml_seq"] + u32(1)
        st = put(st, "ml_seq", mask, seq)
        p_inc = vget(st, "ml_vinc", j)
        for k, v in (("ml_p_active", 1), ("ml_p_target", j),
                     ("ml_p_seq", seq), ("ml_p_inc", p_inc),
                     ("ml_p_exp", 0), ("ml_p_nacks", 0),
                     ("ml_p_to", now + ML_PROBE_TO),
                     ("ml_p_dl", now + ML_PROBE_IV
                      * (st["ml_score"] + 1).astype(i64))):
            st = put(st, k, mask, v)
        st = cnt(st, mask, MLC_PING)
        alive_t = vget(st, "ml_vstate", j) == ML_ALIVE
        rq = piggy_req(rq, mask & alive_t, j, MLM_PING, seq, j, 0)
        buddy = mask & ~alive_t
        parts = one_part(MLM_PING, seq, j, 0)
        parts = parts.at[:, 1, :].set(jnp.stack(
            [jnp.full(H, MLM_SUSPECT, i32), p_inc.astype(i32), j,
             st["ml_self"]], axis=1))
        size = (6 + msg_len(MLM_PING, seq)
                + msg_len(MLM_SUSPECT, p_inc)).astype(i64)
        rq = request(rq, buddy, j, parts, jnp.full(H, 2, i32), size,
                     False, size)
        return st, rq

    # -- the phases -----------------------------------------------------

    def ph_timer(st, rq, m, now):
        cands = [(st["ml_probe_next"], 0, jnp.zeros(H, i64), True),
                 (st["ml_gossip_next"], 1, jnp.zeros(H, i64), True),
                 (st["ml_p_to"], 2, jnp.zeros(H, i64),
                  st["ml_p_active"] == 1),
                 (st["ml_p_dl"], 3, jnp.zeros(H, i64),
                  st["ml_p_active"] == 1)]
        for q in range(RL):
            cands.append((st["ml_rl_dl"][:, q], 4,
                          st["ml_rl_seq"][:, q].astype(i64),
                          st["ml_rl_valid"][:, q] == 1))
        for q in range(SU):
            cands.append((st["ml_su_dl"][:, q], 5,
                          st["ml_su_node"][:, q].astype(i64),
                          st["ml_su_node"][:, q] >= 0))
        t = jnp.stack([c[0] for c in cands], axis=1)
        cls = jnp.asarray([c[1] for c in cands], i32)[None, :]
        key = jnp.stack([c[2] for c in cands], axis=1)
        ok = jnp.stack([jnp.broadcast_to(jnp.asarray(c[3]), (H,))
                        for c in cands], axis=1)
        due = ok & (t <= now[:, None])
        tmin = jnp.where(due, t, NEVER).min(axis=1)
        d2 = due & (t == tmin[:, None])
        cmin = jnp.where(d2, cls, 99).min(axis=1)
        d3 = d2 & (cls == cmin[:, None])
        kmin = jnp.where(d3, key, NEVER).min(axis=1)
        sel = d3 & (key == kmin[:, None])
        anyd = m & due.any(axis=1)
        st = put(st, "ml_pc", m & ~anyd, PC_RECV)
        c0, c1, c2 = (anyd & (cmin == 0), anyd & (cmin == 1),
                      anyd & (cmin == 2))
        c3, c4, c5 = (anyd & (cmin == 3), anyd & (cmin == 4),
                      anyd & (cmin == 5))
        # probe tick
        st = put(st, "ml_probe_next", c0, st["ml_probe_next"] + ML_PROBE_IV)
        busy = c0 & (st["ml_p_active"] == 1)
        st = put(st, "ml_tick", busy, 1)
        go = c0 & ~busy
        st = put(st, "ml_pc", go, PC_WALK)
        st = put(st, "ml_ret", go, PC_TIMER)
        # gossip tick
        st = put(st, "ml_gossip_next", c1,
                 st["ml_gossip_next"] + ML_GOSSIP_IV)
        st = pick(st, c1, ML_GOSSIP_NODES, jnp.full(H, -1, i32), False,
                  now)
        st = put(st, "ml_pc", c1, PC_GOSSIP)
        # probe timeout: the indirect probes
        st = put(st, "ml_p_to", c2, NEVER)
        st = pick(st, c2, ML_INDIRECT, st["ml_p_target"], True, now)
        st = put(st, "ml_pc", c2, PC_PREQ)
        # probe deadline: failed
        delta = jnp.where(st["ml_p_exp"] > 0,
                          jnp.maximum(0, st["ml_p_exp"] - st["ml_p_nacks"]),
                          1)
        st = put(st, "ml_p_active", c3, 0)
        st = suspect(st, c3, st["ml_p_inc"], st["ml_p_target"],
                     st["ml_self"], now)
        st = probe_end(st, c3, delta, PC_TIMER)
        # relayed probe's nack timeout
        rsel = sel[:, 4:4 + RL] & c4[:, None]
        r_from = tget(st, "ml_rl_from", rsel)
        r_oseq = tget(st, "ml_rl_oseq", rsel)
        r_nack = tget(st, "ml_rl_nack", rsel) == 1
        st = upd(st, ml_rl_valid=jnp.where(rsel, 0, st["ml_rl_valid"]))
        nk = c4 & r_nack
        st = cnt(st, nk, MLC_NACK)
        rq = piggy_req(rq, nk, r_from, MLM_NACK, r_oseq, jnp.zeros(H, i32),
                       0)
        # suspicion timeout
        node = kmin.astype(i32)
        st = del_table(st, "ml_su_node", c5, node)
        st = dead(st, c5, vget(st, "ml_vinc", node), node, st["ml_self"],
                  now)
        return st, rq

    def ph_walk(st, rq, m, now):
        st, found, tgt = walk(st, m, now)
        st = put(st, "ml_pc", m, st["ml_ret"])
        st, rq = probe_node(st, rq, found, tgt, now)
        return st, rq

    def ph_gossip(st, rq, m, now):
        done = m & (st["ml_sub"] >= st["ml_npk"])
        st = put(st, "ml_pc", done, PC_TIMER)
        go = m & ~done
        parts0 = jnp.zeros((H, NP, 4), i32)
        st, parts, n, used = get(st, go, jnp.full(H, ML_UDP_BUF - 2, i64),
                                 parts0, jnp.zeros(H, i32))
        none = go & (n == 0)
        st = put(st, "ml_pc", none, PC_TIMER)
        snd = go & (n > 0)
        dst = st["ml_pk"][hidx, jnp.clip(st["ml_sub"], 0, ML_INDIRECT - 1)]
        size = jnp.where(n == 1, msg_len(parts[:, 0, 0], parts[:, 0, 1])
                         .astype(i64), 2 + used)
        rq = request(rq, snd, dst, parts, n, size, False, size)
        st = put(st, "ml_sub", snd, st["ml_sub"] + 1)
        return st, rq

    def ph_preq(st, rq, m, now):
        done = m & (st["ml_sub"] >= st["ml_npk"])
        st = put(st, "ml_pc", done, PC_TIMER)
        go = m & ~done
        dst = st["ml_pk"][hidx, jnp.clip(st["ml_sub"], 0, ML_INDIRECT - 1)]
        st = put(st, "ml_p_exp", go, st["ml_p_exp"] + 1)
        st = cnt(st, go, MLC_PINGREQ)
        rq = piggy_req(rq, go, dst, MLM_PINGREQ, st["ml_p_seq"],
                       st["ml_p_target"], 1)
        return put(st, "ml_sub", go, st["ml_sub"] + 1), rq

    def ph_recv(st, m, now):
        st = upd(st, app_sys=st["app_sys"].at[:, hx.ASYS_RECVFROM].add(
            jnp.where(m, 1, 0)))
        empty = m & (st["rq_len"] <= st["rq_pos"])
        st = put(st, "ml_pc", empty, PC_DONE)
        got = m & ~empty
        pos = st["rq_pos"] & (hx.R - 1)
        src = st["rq_srchost"][hidx, pos]
        pseq = st["rq_pseq"][hidx, pos]
        plen = st["rq_plen"][hidx, pos]
        st = put(st, "rq_pos", got, st["rq_pos"] + 1)
        st = put(st, "recv_bytes", got,
                 st["recv_bytes"] - (plen.astype(i64) + 28))
        now_empty = got & (st["rq_len"] <= st["rq_pos"])
        st = hx.set_status(st, u32(0), u32(hx.S_READABLE), now_empty, now)
        srow = jnp.clip(src, 0, H - 1)
        slot = slot_of(pseq)
        bad = got & (st["ml_pay_tag"][srow, slot] != pseq)
        st = hx.mark_abort(st, bad.any(), hx.AB_STRUCT)
        st = upd(st, ml_cur=jnp.where(got[:, None, None],
                                      st["ml_pay"][srow, slot],
                                      st["ml_cur"]))
        st = put(st, "ml_cur_n", got, st["ml_pay_n"][srow, slot])
        st = put(st, "ml_from", got, st["ml_self"][srow])
        st = put(st, "ml_ci", got, 0)
        return put(st, "ml_pc", got, PC_PART)

    def ph_part(st, rq, m, now):
        fin = m & (st["ml_ci"] >= st["ml_cur_n"])
        st = put(st, "ml_pc", fin, PC_RECV)
        go = m & ~fin
        ci = jnp.clip(st["ml_ci"], 0, NP - 1)
        part = st["ml_cur"][hidx, ci]
        kind, a, b, c = part[:, 0], part[:, 1], part[:, 2], part[:, 3]
        frm = st["ml_from"]
        st = put(st, "ml_ci", go, st["ml_ci"] + 1)
        au = a.astype(u32)
        # ping: ack it
        ping = go & (kind == MLM_PING) & (b == st["ml_self"])
        st = cnt(st, ping, MLC_ACK)
        rq = piggy_req(rq, ping, frm, MLM_ACK, au, jnp.zeros(H, i32), 0)
        # ping-req: relay a ping of our own
        preq = go & (kind == MLM_PINGREQ)
        seq = st["ml_seq"] + u32(1)
        st = put(st, "ml_seq", preq, seq)
        st = insert(st, "ml_rl_", "ml_rl_valid", 0, preq, {
            "valid": jnp.ones(H, i32), "seq": seq, "oseq": au,
            "from": frm, "nack": c, "dl": now + ML_PROBE_TO})
        st = cnt(st, preq, MLC_PING)
        rq = piggy_req(rq, preq, b, MLM_PING, seq, b, 0)
        # ack: our probe's, or a relayed one's
        ack = go & (kind == MLM_ACK)
        mine = ack & (st["ml_p_active"] == 1) & (au == st["ml_p_seq"])
        st = probe_end(st, mine, -1, PC_PART)
        rl = ((st["ml_rl_seq"] == au[:, None]) & (st["ml_rl_valid"] == 1)
              & (ack & ~mine)[:, None])
        rl = col_of(rl) & rl
        hit = rl.any(axis=1)
        r_from = tget(st, "ml_rl_from", rl)
        r_oseq = tget(st, "ml_rl_oseq", rl)
        st = upd(st, ml_rl_valid=jnp.where(rl, 0, st["ml_rl_valid"]))
        st = cnt(st, hit, MLC_ACK)
        rq = piggy_req(rq, hit, r_from, MLM_ACK, r_oseq, jnp.zeros(H, i32),
                       0)
        # nack
        nk = (go & (kind == MLM_NACK) & (st["ml_p_active"] == 1)
              & (au == st["ml_p_seq"]))
        st = put(st, "ml_p_nacks", nk, st["ml_p_nacks"] + 1)
        # updates
        with hx.stage(hx.KS_MERGE):
            st = hx.ks_count(st, hx.KS_MERGE, go & (kind >= MLM_SUSPECT))
            st = suspect(st, go & (kind == MLM_SUSPECT), au, b, c, now)
            st = dead(st, go & (kind == MLM_DEAD), au, b, c, now)
            st = alive(st, go & (kind == MLM_ALIVE), au, b)
        return st, rq

    def ph_done(st, m, now):
        t = jnp.minimum(st["ml_probe_next"], st["ml_gossip_next"])
        act = st["ml_p_active"] == 1
        t = jnp.where(act, jnp.minimum(t, jnp.minimum(st["ml_p_to"],
                                                      st["ml_p_dl"])), t)
        t = jnp.minimum(t, jnp.where(st["ml_rl_valid"] == 1, st["ml_rl_dl"],
                                     NEVER).min(axis=1))
        t = jnp.minimum(t, jnp.where(st["ml_su_node"] >= 0, st["ml_su_dl"],
                                     NEVER).min(axis=1))
        arm = m & (t < st["ml_armed"])
        st = put(st, "ml_armed", arm, t)
        st, sq = hx.draw_seq(st, arm)
        st = hx.th_push(st, arm, t, sq, hx.TK_APP, 0)
        st = put(st, "m_waitmask", m, jnp.uint32(hx.S_READABLE))
        st = put(st, "m_waitseq", m, st["park_ctr"])
        st = put(st, "park_ctr", m, st["park_ctr"] + 1)
        st = put(st, "cont", m, hx.C_IDLE)
        return put(st, "ml_pc", m, PC_ENTRY)

    # -- the send tail --------------------------------------------------

    def send(st, rq, now):
        want = rq["want"]
        parts, n = rq["parts"], rq["n"]
        pg = want & rq["piggy"]
        limit = ML_UDP_BUF - rq["mlen"] - 2
        st, parts, n, used = jax.lax.cond(
            (pg & (st["ml_bq_node"] >= 0).any(axis=1)).any(),
            lambda s: get(s, pg, limit, parts, n),
            lambda s: (s, parts, n, jnp.zeros(H, i64)), st)
        size = jnp.where(pg & (n > 1), 4 + rq["mlen"] + used, rq["size"])
        st = upd(st, app_sys=st["app_sys"].at[:, hx.ASYS_SENDTO].add(
            jnp.where(want, 1, 0)))
        psize = size + 28
        over = want & (st["send_bytes"] + psize > st["send_max"])
        # a full send buffer loses the datagram
        st = hx.set_status(st, u32(0), u32(hx.S_WRITABLE), over, now)
        sent = want & ~over
        pseq = st["packet_seq"]
        st = put(st, "packet_seq", sent, pseq + 1)
        st = hx.mark_abort(st, (sent & (st["sq_len"] - st["sq_pos"]
                                        >= hx.S - 1)).any(), hx.AB_STRUCT)
        tail = st["sq_len"] & (hx.S - 1)
        rows = mrows(sent)
        vals = {"srchost": hidx, "pseq": pseq, "sip": st["eth_ip"],
                "sport": st["m_port"],
                "dip": st["ml_ips"][jnp.clip(rq["dst"], 0, N - 1)],
                "dport": st["m_port"], "plen": size.astype(i32)}
        st = dict(st)
        for kk in hx.PKF:
            st[f"sq_{kk}"] = hx.scatter_set(st[f"sq_{kk}"], (rows, tail),
                                            vals[kk])
        slot = slot_of(pseq)
        st["ml_pay"] = st["ml_pay"].at[rows, slot].set(parts, mode="drop")
        st["ml_pay_n"] = st["ml_pay_n"].at[rows, slot].set(n, mode="drop")
        st["ml_pay_tag"] = hx.scatter_set(st["ml_pay_tag"], (rows, slot),
                                          pseq)
        st = put(st, "sq_len", sent, st["sq_len"] + 1)
        st = put(st, "send_bytes", sent, st["send_bytes"] + psize)
        newly = sent & (st["queued"] == 0)
        st = put(st, "queued", newly, 1)
        notify = newly & (st["r1_pending"] == 0)
        st = put(st, "cont", notify, hx.C_R1)
        return put(st, "then", notify, hx.C_M_STEP)

    def op_ml(st, mask):
        now = st["now"]
        rq = {"want": jnp.zeros(H, bool), "dst": jnp.zeros(H, i32),
              "parts": jnp.zeros((H, NP, 4), i32),
              "n": jnp.zeros(H, i32), "size": jnp.zeros(H, i64),
              "piggy": jnp.zeros(H, bool), "mlen": jnp.zeros(H, i64)}

        def at(pc):
            return mask & (st["ml_pc"] == pc) & ~rq["want"]

        def guarded(fn, m, st, rq):
            return jax.lax.cond(m.any(), fn, lambda s, r, _m: (s, r),
                                st, rq, m)

        # Phases that write the (H, N) view run masked, outside any
        # lax.cond: a branch that writes a buffer the other passes
        # through makes XLA copy the whole view on every iteration.
        with hx.stage(hx.KS_PROBE):
            m = at(PC_ENTRY)
            st = put(st, "ml_armed", m & (st["ml_armed"] <= now), NEVER)
            st = put(st, "ml_pc", m, PC_TIMER)
            m = at(PC_TIMER)
            st = hx.ks_count(st, hx.KS_PROBE, m)
            st, rq = ph_timer(st, rq, m, now)
            st, rq = ph_walk(st, rq, at(PC_WALK), now)
        with hx.stage(hx.KS_GOSSIP):
            m = at(PC_GOSSIP)
            st = hx.ks_count(st, hx.KS_GOSSIP, m)
            st, rq = guarded(lambda s, r, mm: ph_gossip(s, r, mm, now), m,
                             st, rq)
        with hx.stage(hx.KS_PROBE):
            st, rq = ph_preq(st, rq, at(PC_PREQ), now)
        with hx.stage(hx.KS_MERGE):
            st = ph_recv(st, at(PC_RECV), now)
            st, rq = ph_part(st, rq, at(PC_PART), now)
        with hx.stage(hx.KS_PROBE):
            st = ph_done(st, at(PC_DONE), now)
        return jax.lax.cond(rq["want"].any(), lambda s: send(s, rq, now),
                            lambda s: s, st)

    def check_inflight(st):
        """Abort unless every datagram still queued or in flight keeps
        its payload slot (the import rebuilds it from there)."""
        bad = jnp.zeros((), bool)
        for pfx, cap, pos, ln, modulo in (
                ("rq", hx.R, "rq_pos", "rq_len", True),
                ("sq", hx.S, "sq_pos", "sq_len", True),
                ("cq", hx.C, "cq_pos", "cq_len", True),
                ("ib", hx.I, "ib_pos", "ib_len", False)):
            k = jnp.arange(cap)[None, :]
            off = (k - st[pos][:, None]) & (cap - 1) if modulo else \
                k - st[pos][:, None]
            live = (off >= 0) & (off < (st[ln] - st[pos])[:, None])
            src = jnp.clip(st[f"{pfx}_srchost"], 0, H - 1)
            ps = st[f"{pfx}_pseq"]
            tag = st["ml_pay_tag"][src, slot_of(ps)]
            bad = bad | (live & (tag != ps)).any()
        for r in (1, 2):
            src = jnp.clip(st[f"r{r}_pk_srchost"], 0, H - 1)
            ps = st[f"r{r}_pk_pseq"]
            tag = st["ml_pay_tag"][src, slot_of(ps)]
            bad = bad | ((st[f"r{r}_pk_valid"] == 1) & (tag != ps)).any()
        return hx.mark_abort(st, bad, hx.AB_STRUCT)

    return op_ml, check_inflight
