"""Mutation self-test: the twin-contract gate actually bites.

Perturbs one twin constant and one SoA column (in-memory, via the
extractor API's cpp_text injection — the tree is never touched) and
asserts the corresponding pass fails.  A lint gate that cannot detect
an injected drift is worse than none: it certifies clean trees it
never checked.
"""

import os

import pytest

from shadow_tpu.analysis import soa_layout, twin_constants

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def cpp_text():
    with open(os.path.join(ROOT, "native", "netplane.cpp")) as fh:
        return fh.read()


@pytest.fixture(scope="module")
def shim_text():
    with open(os.path.join(ROOT, "native", "shim.c")) as fh:
        return fh.read()


def _mutate(text: str, old: str, new: str, count: int = 1) -> str:
    assert text.count(old) == count, \
        f"mutation anchor count != {count}: {old!r}"
    return text.replace(old, new)


def test_constant_value_drift_is_caught(cpp_text):
    mutated = _mutate(cpp_text, "constexpr int MSS = 1460;",
                      "constexpr int MSS = 1461;")
    v = twin_constants.check(ROOT, cpp_text=mutated)
    assert any("MSS" in x.message and "1461" in x.message for x in v), \
        [x.render() for x in v]


def test_constant_removal_is_caught(cpp_text):
    mutated = _mutate(cpp_text, "constexpr int64_t DELACK_NS",
                      "constexpr int64_t DELACK2_NS")
    v = twin_constants.check(ROOT, cpp_text=mutated)
    assert any(x.message.startswith("C++ constant DELACK_NS")
               for x in v), [x.render() for x in v]


def test_enum_reorder_is_caught(cpp_text):
    # swapping two TCP states shifts every later enum value
    mutated = _mutate(cpp_text, "ST_ESTABLISHED,\n  ST_FIN_WAIT_1",
                      "ST_FIN_WAIT_1,\n  ST_ESTABLISHED")
    v = twin_constants.check(ROOT, cpp_text=mutated)
    assert any("ESTABLISHED" in x.message for x in v), \
        [x.render() for x in v]


def test_tel_enum_drift_is_caught(cpp_text):
    # swapping two drop causes shifts their values: the trace/events
    # twins (and the phold kernel's slots) must flag both
    mutated = _mutate(cpp_text, "TEL_NO_ROUTE, TEL_NO_SOCKET,",
                      "TEL_NO_SOCKET, TEL_NO_ROUTE,")
    v = twin_constants.check(ROOT, cpp_text=mutated)
    assert any("TEL_NO_ROUTE" in x.message for x in v), \
        [x.render() for x in v]


def test_tel_cause_table_reorder_is_caught(cpp_text):
    # reordering TEL_NAMES without touching the enum desynchronizes
    # the attribution report's labels from the counters
    mutated = _mutate(cpp_text,
                      '    "loss-edge",\n    "unreachable",',
                      '    "unreachable",\n    "loss-edge",')
    v = twin_constants.check(ROOT, cpp_text=mutated)
    assert any("TEL_NAMES" in x.message for x in v), \
        [x.render() for x in v]


def test_unregistered_tel_constant_is_caught(cpp_text):
    # a new TEL_* member with no contract row must fail closed — a
    # half-registered drop cause could never conserve
    mutated = _mutate(cpp_text, "constexpr int TEL_WIRE_N = 13;",
                      "constexpr int TEL_WIRE_N = 13;\n"
                      "constexpr int TEL_BOGUS = 99;")
    v = twin_constants.check(ROOT, cpp_text=mutated)
    assert any("TEL_BOGUS" in x.message for x in v), \
        [x.render() for x in v]


def test_column_rename_is_caught(cpp_text):
    mutated = _mutate(cpp_text, 'put("c_cwnd", bytes_vec(c_cwnd));',
                      'put("c_cwndx", bytes_vec(c_cwnd));')
    v = soa_layout.check(ROOT, cpp_text=mutated)
    msgs = [x.message for x in v]
    # both directions fire: a dead exported column and a phantom read
    assert any("'c_cwndx'" in m and "never consumed" in m for m in msgs), msgs
    assert any("'c_cwnd'" in m and "never exports" in m for m in msgs), msgs


def test_column_dtype_drift_is_caught(cpp_text):
    mutated = _mutate(cpp_text,
                      "std::vector<int64_t> cq_enq(H * C, 0);",
                      "std::vector<int32_t> cq_enq(H * C, 0);")
    v = soa_layout.check(ROOT, cpp_text=mutated)
    assert any("'cq_enq'" in x.message and "int32" in x.message
               for x in v), [x.render() for x in v]


def test_import_column_loss_is_caught(cpp_text):
    # import stops reading a column the codec produces
    mutated = _mutate(
        cpp_text,
        'const int64_t *c_cwnd = col<int64_t>(d, "c_cwnd", CC, &ok);',
        'const int64_t *c_cwnd = col<int64_t>(d, "c_cwndx", CC, &ok);')
    v = soa_layout.check(ROOT, cpp_text=mutated)
    msgs = [x.message for x in v]
    assert any("'c_cwndx'" in m and "never produces" in m for m in msgs), msgs


def test_unclassified_residency_column_is_caught(tmp_path, monkeypatch):
    """Dirty-column protocol: a state column added to the codec
    without a RESIDENT_* classification entry must fail pass 2."""
    path = os.path.join(ROOT, "shadow_tpu", "ops", "phold_span.py")
    with open(path) as fh:
        src = fh.read()
    mutated = _mutate(
        src, '        st["out_first"] = np.zeros(H, np.int32)',
        '        st["out_first"] = np.zeros(H, np.int32)\n'
        '        st["rogue_col"] = np.zeros(H, np.int32)')
    mpath = tmp_path / "phold_span.py"
    mpath.write_text(mutated)
    monkeypatch.setitem(soa_layout.FAMILIES[0], "codec", str(mpath))
    v = soa_layout.check(ROOT)
    assert any("rogue_col" in x.message and "residency" in x.message
               for x in v), [x.message for x in v]


def test_stale_residency_entry_is_caught(tmp_path, monkeypatch):
    """The reverse direction: a classification entry naming a column
    the codec no longer produces must fail pass 2."""
    path = os.path.join(ROOT, "shadow_tpu", "ops", "phold_span.py")
    with open(path) as fh:
        src = fh.read()
    # drop the column from the codec but leave it classified
    mutated = _mutate(
        src,
        '"packet_seq", "recv_bytes",\n                  "recv_max"',
        '"packet_seq",\n                  "recv_max"')
    mpath = tmp_path / "phold_span.py"
    mpath.write_text(mutated)
    monkeypatch.setitem(soa_layout.FAMILIES[0], "codec", str(mpath))
    v = soa_layout.check(ROOT)
    assert any("recv_bytes" in x.message for x in v), \
        [x.message for x in v]


def test_trace_record_layout_drift_is_caught(cpp_text):
    """Flight-record layout drift (ISSUE 4): a resized record would
    desynchronize the engine ring from trace/events.py REC."""
    mutated = _mutate(cpp_text, "constexpr int FLIGHT_REC_BYTES = 32;",
                      "constexpr int FLIGHT_REC_BYTES = 40;")
    v = twin_constants.check(ROOT, cpp_text=mutated)
    assert any("FLIGHT_REC_BYTES" in x.message and "40" in x.message
               for x in v), [x.render() for x in v]


def test_tel_record_size_drift_is_caught(cpp_text):
    """The telemetry record grew to 104 B for the per-flow `marks`
    column (ISSUE 12); a drifted size — e.g. a field added on one
    side only — must flag, exactly like the other record pins."""
    mutated = _mutate(cpp_text, "constexpr int TEL_REC_BYTES = 104;",
                      "constexpr int TEL_REC_BYTES = 112;")
    v = twin_constants.check(ROOT, cpp_text=mutated)
    assert any("TEL_REC_BYTES" in x.message and "112" in x.message
               for x in v), [x.render() for x in v]


def test_ceseen_codec_column_rename_is_caught(cpp_text):
    """The c_ceseen span-codec column (per-flow mark telemetry) is
    4-side checked by pass 2: renaming the export put() must fail
    the import/export cross-check."""
    from shadow_tpu.analysis import soa_layout
    mutated = _mutate(cpp_text, 'put("c_ceseen", bytes_vec(c_ceseen));',
                      'put("c_seen", bytes_vec(c_ceseen));')
    v = soa_layout.check(ROOT, cpp_text=mutated)
    assert any("c_ceseen" in x.message or "c_seen" in x.message
               for x in v), [x.render() for x in v]


def test_trace_event_enum_reorder_is_caught(cpp_text):
    """Swapping two FR_* members shifts every later value — the
    implicit-increment extraction must surface the drift."""
    mutated = _mutate(
        cpp_text, "FR_ROUND = 0, FR_SPAN_START, FR_SPAN_COMMIT",
        "FR_ROUND = 0, FR_SPAN_COMMIT, FR_SPAN_START")
    v = twin_constants.check(ROOT, cpp_text=mutated)
    assert any("FR_SPAN" in x.message for x in v), \
        [x.render() for x in v]


def test_unregistered_trace_enum_fails_closed(cpp_text):
    """A new EL_* reason added engine-side without a contract row (and
    a Python twin) must fail the pass, not silently under-check."""
    mutated = _mutate(cpp_text, "EL_SVC_QUIESCENT, EL_N,",
                      "EL_SVC_QUIESCENT, EL_ROGUE, EL_N,")
    v = twin_constants.check(ROOT, cpp_text=mutated)
    msgs = [x.message for x in v]
    assert any("EL_ROGUE" in m and "no contract row" in m
               for m in msgs), msgs


def test_trace_reason_table_reorder_is_caught(cpp_text):
    """Reordering EL_NAMES alone (enum untouched) must be caught by
    the string-table twin check."""
    mutated = _mutate(
        cpp_text,
        '"engine-span:routed",\n    "engine-span:cold-budget",',
        '"engine-span:cold-budget",\n    "engine-span:routed",')
    v = twin_constants.check(ROOT, cpp_text=mutated)
    assert any("EL_NAMES" in x.message for x in v), \
        [x.render() for x in v]


def test_fb_flag_drift_is_caught(cpp_text):
    """Fabric-observatory activity-mask drift (ISSUE 8): changing an
    FB_ACT_* bit would silently change which hosts sample — every
    twin (trace/events + both device kernels) must flag."""
    mutated = _mutate(cpp_text, "constexpr int FB_ACT_TB_OUT = 2;",
                      "constexpr int FB_ACT_TB_OUT = 16;")
    v = twin_constants.check(ROOT, cpp_text=mutated)
    msgs = [x.message for x in v]
    assert sum("FB_ACT_TB_OUT" in m for m in msgs) >= 3, msgs


def test_fb_record_size_drift_is_caught(cpp_text):
    """A resized fabric record would desynchronize the engine ring
    from trace/events.py FB_REC — the size pin must flag (FCT_REC is
    pinned the same way)."""
    mutated = _mutate(cpp_text, "constexpr int FB_REC_BYTES = 128;",
                      "constexpr int FB_REC_BYTES = 136;")
    v = twin_constants.check(ROOT, cpp_text=mutated)
    assert any("FB_REC_BYTES" in x.message and "136" in x.message
               for x in v), [x.render() for x in v]
    mutated = _mutate(cpp_text, "constexpr int FCT_REC_BYTES = 64;",
                      "constexpr int FCT_REC_BYTES = 72;")
    v = twin_constants.check(ROOT, cpp_text=mutated)
    assert any("FCT_REC_BYTES" in x.message and "72" in x.message
               for x in v), [x.render() for x in v]


def test_unregistered_fb_constant_fails_closed(cpp_text):
    """A new FB_*/FCT_* member added engine-side without a contract
    row (and a Python twin) must fail the pass, not silently
    under-check."""
    mutated = _mutate(cpp_text, "constexpr int FB_ACT_LINK = 8;",
                      "constexpr int FB_ACT_LINK = 8;\n"
                      "constexpr int FB_ROGUE = 99;\n"
                      "constexpr int FCT_ROGUE = 98;")
    v = twin_constants.check(ROOT, cpp_text=mutated)
    msgs = [x.message for x in v]
    assert any("FB_ROGUE" in m and "no contract row" in m
               for m in msgs), msgs
    assert any("FCT_ROGUE" in m and "no contract row" in m
               for m in msgs), msgs


def test_fabric_column_rename_is_caught(cpp_text):
    """The fabric counters ride the span codecs: renaming an export
    column must fail pass 2 in both directions (dead export + phantom
    read), exactly like the pre-existing columns."""
    mutated = _mutate(cpp_text,
                      'put("codel_enq_bytes", bytes_vec(codel_enq_bytes));\n'
                      '  put("codel_drop_bytes", bytes_vec(codel_drop_bytes));\n'
                      '  put("codel_peak", bytes_vec(codel_peak));\n'
                      '  put("codel_marked", bytes_vec(codel_marked));\n'
                      '  for (int ri = 1; ri <= 2; ri++) {',
                      'put("codel_enq_bytesx", bytes_vec(codel_enq_bytes));\n'
                      '  put("codel_drop_bytes", bytes_vec(codel_drop_bytes));\n'
                      '  put("codel_peak", bytes_vec(codel_peak));\n'
                      '  put("codel_marked", bytes_vec(codel_marked));\n'
                      '  for (int ri = 1; ri <= 2; ri++) {')
    v = soa_layout.check(ROOT, cpp_text=mutated)
    msgs = [x.message for x in v]
    assert any("'codel_enq_bytesx'" in m and "never consumed" in m
               for m in msgs), msgs
    assert any("'codel_enq_bytes'" in m and "never exports" in m
               for m in msgs), msgs


def test_sc_enum_drift_is_caught(shim_text):
    """Syscall-observatory disposition drift (ISSUE 7): swapping two
    SC_* members in the shim shifts their values — every trace/events
    twin must flag."""
    mutated = _mutate(shim_text, "SC_PARKED = 1,", "SC_PARKED = 2,")
    mutated = _mutate(mutated, "SC_NATIVE = 2,", "SC_NATIVE = 1,")
    v = twin_constants.check(ROOT, shim_text=mutated)
    msgs = [x.message for x in v]
    assert any("SC_PARKED" in m for m in msgs), msgs
    assert any("SC_NATIVE" in m for m in msgs), msgs


def test_sc_record_size_drift_is_caught(shim_text):
    """A resized syscall record would desynchronize syscalls-sim.bin
    from trace/events.py SC_REC — the size pin must flag."""
    mutated = _mutate(shim_text, "SC_REC_BYTES = 40,",
                      "SC_REC_BYTES = 48,")
    v = twin_constants.check(ROOT, shim_text=mutated)
    assert any("SC_REC_BYTES" in x.message and "48" in x.message
               for x in v), [x.render() for x in v]


def test_sc_ipc_layout_drift_is_caught(shim_text):
    """Moving the shim's sc_local counter without updating the
    manager's mmap offset (shim_abi.CHAN_SC_LOCAL) would silently
    read garbage — the layout twin must flag.  (In a real build the
    _Static_assert catches the struct side too.)"""
    mutated = _mutate(shim_text, "SC_CHAN_LOCAL_OFF = 280,",
                      "SC_CHAN_LOCAL_OFF = 288,")
    v = twin_constants.check(ROOT, shim_text=mutated)
    assert any("SC_CHAN_LOCAL_OFF" in x.message for x in v), \
        [x.render() for x in v]


def test_svc_flags_offset_drift_is_caught(shim_text):
    """Moving the v8 svc_flags header word without updating the
    manager's mmap offset (shim_abi.OFF_SVC) would make the service-
    plane advertisement write into header padding — the layout twin
    must flag (ISSUE 13)."""
    mutated = _mutate(shim_text, "SC_SVC_FLAGS_OFF = 528,",
                      "SC_SVC_FLAGS_OFF = 532,")
    v = twin_constants.check(ROOT, shim_text=mutated)
    assert any("SC_SVC_FLAGS_OFF" in x.message for x in v), \
        [x.render() for x in v]


def test_unregistered_sc_constant_fails_closed(shim_text):
    """A new SC_* member added shim-side without a contract row (and
    a trace/events.py twin) must fail the pass."""
    mutated = _mutate(shim_text, "SC_N = 5,",
                      "SC_N = 5,\n    SC_ROGUE = 99,")
    v = twin_constants.check(ROOT, shim_text=mutated)
    msgs = [x.message for x in v]
    assert any("SC_ROGUE" in m and "no contract row" in m
               for m in msgs), msgs


def test_sc_constant_removal_is_caught(shim_text):
    """Renaming an SC_* member away breaks the contract row — the
    extractor-miss direction must also fail."""
    mutated = _mutate(shim_text, "SC_SHIM = 3,", "SC_SHIMX = 3,")
    v = twin_constants.check(ROOT, shim_text=mutated)
    msgs = [x.message for x in v]
    assert any(m.startswith("C++ constant SC_SHIM") for m in msgs), msgs
    assert any("SC_SHIMX" in m and "no contract row" in m
               for m in msgs), msgs


# ---------------------------------------------------------------------
# Checkpoint framing constants (CK_*; shadow_tpu/ckpt/format.py twins,
# docs/CHECKPOINT.md).  The plane blob's header constants must never
# drift silently: a mismatched magic/version/header-size would misparse
# every snapshot — or worse, accept one written by a different build.


def test_ck_layout_version_drift_is_caught(cpp_text):
    mutated = _mutate(cpp_text,
                      "constexpr uint32_t CK_PLANE_VERSION = 3;",
                      "constexpr uint32_t CK_PLANE_VERSION = 4;")
    v = twin_constants.check(ROOT, cpp_text=mutated)
    assert any("CK_PLANE_VERSION" in x.message for x in v), \
        [x.render() for x in v]


def test_ck_section_size_drift_is_caught(cpp_text):
    """Frame-header width drift (the 'section size' of the plane
    blob's framing) must be flagged against the Python parser twin."""
    mutated = _mutate(cpp_text,
                      "constexpr int CK_FRAME_HDR_BYTES = 12;",
                      "constexpr int CK_FRAME_HDR_BYTES = 16;")
    v = twin_constants.check(ROOT, cpp_text=mutated)
    assert any("CK_FRAME_HDR_BYTES" in x.message for x in v), \
        [x.render() for x in v]


def test_unregistered_ck_constant_fails_closed(cpp_text):
    """A new CK_* constant without a contract row (and a ckpt/format.py
    twin) must fail the pass — the prefix is fail-closed like
    FR_*/EL_*/TEL_*."""
    mutated = _mutate(cpp_text,
                      "constexpr uint32_t CK_GLOBAL_FRAME",
                      "constexpr uint32_t CK_ROGUE = 7;\n"
                      "constexpr uint32_t CK_GLOBAL_FRAME")
    v = twin_constants.check(ROOT, cpp_text=mutated)
    msgs = [x.message for x in v]
    assert any("CK_ROGUE" in m and "no contract row" in m
               for m in msgs), msgs


def test_fault_flight_kind_drift_is_caught(cpp_text):
    """FR_FAULT_* ride the fail-closed FR_ namespace: reordering the
    fault kinds must be flagged against trace/events.py."""
    mutated = _mutate(cpp_text,
                      "FR_FAULT_KILL, FR_FAULT_RESTORE,",
                      "FR_FAULT_RESTORE, FR_FAULT_KILL,")
    v = twin_constants.check(ROOT, cpp_text=mutated)
    assert any("FR_FAULT" in x.message for x in v), \
        [x.render() for x in v]


def test_tel_host_down_drift_is_caught(cpp_text):
    """The fault drop causes sit mid-enum: swapping them shifts the
    cause codes and must be flagged against every TEL_* twin."""
    mutated = _mutate(cpp_text, "TEL_HOST_DOWN, TEL_LINK_DOWN,",
                      "TEL_LINK_DOWN, TEL_HOST_DOWN,")
    v = twin_constants.check(ROOT, cpp_text=mutated)
    assert any("TEL_HOST_DOWN" in x.message or
               "TEL_LINK_DOWN" in x.message for x in v), \
        [x.render() for x in v]


def test_dctcp_k_drift_is_caught(cpp_text):
    # a drifted marking threshold silently desynchronizes which
    # packets the three paths mark CE
    mutated = _mutate(cpp_text, "constexpr int64_t DCTCP_K_PKTS = 20;",
                      "constexpr int64_t DCTCP_K_PKTS = 21;")
    v = twin_constants.check(ROOT, cpp_text=mutated)
    assert any("DCTCP_K_PKTS" in x.message and "21" in x.message
               for x in v), [x.render() for x in v]


def test_dctcp_alpha_shift_drift_is_caught(cpp_text):
    # the alpha EWMA is fixed-point: a shifted gain changes every
    # cwnd reduction bit-for-bit
    mutated = _mutate(cpp_text,
                      "constexpr int64_t DCTCP_G_SHIFT = 4;",
                      "constexpr int64_t DCTCP_G_SHIFT = 5;")
    v = twin_constants.check(ROOT, cpp_text=mutated)
    assert any("DCTCP_G_SHIFT" in x.message for x in v), \
        [x.render() for x in v]


def test_ecn_flag_bit_swap_is_caught(cpp_text):
    # swapping ECE/CWR bit values flips negotiation and echo on one
    # side only
    mutated = _mutate(cpp_text,
                      "constexpr int F_ECE = 0x40;\n"
                      "constexpr int F_CWR = 0x80;",
                      "constexpr int F_ECE = 0x80;\n"
                      "constexpr int F_CWR = 0x40;")
    v = twin_constants.check(ROOT, cpp_text=mutated)
    assert any("F_ECE" in x.message for x in v), \
        [x.render() for x in v]
    assert any("F_CWR" in x.message for x in v), \
        [x.render() for x in v]


def test_unregistered_mark_cause_fails_closed(cpp_text):
    # extending the MARK_* attribution without registering the twin
    # must be a violation in itself
    mutated = _mutate(cpp_text,
                      "enum { MARK_THRESH_PKTS = 0, MARK_THRESH_BYTES,"
                      " MARK_N };",
                      "enum { MARK_THRESH_PKTS = 0, MARK_THRESH_BYTES,"
                      " MARK_CODEL_LAW, MARK_N };")
    v = twin_constants.check(ROOT, cpp_text=mutated)
    assert any("MARK_CODEL_LAW" in x.message and "no contract row"
               in x.message for x in v), [x.render() for x in v]


def test_mark_name_table_reorder_is_caught(cpp_text):
    # reordering MARK_NAMES without touching the enum desynchronizes
    # the fabric ledger's labels from the counters
    mutated = _mutate(cpp_text,
                      '    "dctcp-k-pkts",\n    "dctcp-k-bytes",',
                      '    "dctcp-k-bytes",\n    "dctcp-k-pkts",')
    v = twin_constants.check(ROOT, cpp_text=mutated)
    assert any("MARK_NAMES" in x.message for x in v), \
        [x.render() for x in v]


def test_el_shard_name_table_drift_is_caught(cpp_text):
    # the new shard-routing reason strings (ISSUE 11) must stay in
    # lockstep with trace/events.py EL_NAMES — the eligibility report
    # and the sharded bench rungs render through them
    mutated = _mutate(cpp_text, '    "device-span:sharded",',
                      '    "device-span-sharded",')
    v = twin_constants.check(ROOT, cpp_text=mutated)
    assert any("EL_NAMES" in x.message for x in v), \
        [x.render() for x in v]


def test_el_shard_enum_drift_is_caught(cpp_text):
    # renaming a shard-routing EL code desynchronizes the audit's
    # attribution (missing registered twin + unregistered EL_ member,
    # both fail-closed)
    mutated = _mutate(cpp_text,
                      "EL_ENGINE_EXCHANGE, EL_ENGINE_UNSHARDED,",
                      "EL_ENGINE_EXCHANGE2, EL_ENGINE_UNSHARDED,")
    v = twin_constants.check(ROOT, cpp_text=mutated)
    assert any("EL_ENGINE_EXCHANGE" in x.message for x in v), \
        [x.render() for x in v]


def test_h_fault_column_rename_is_caught(cpp_text):
    """The down-host fault mask (docs/ROBUSTNESS.md) rides the
    4-side-checked span codecs: renaming the export column must fire
    both directions (dead export + phantom codec read) — in BOTH
    device-span families, which each export it once."""
    mutated = _mutate(cpp_text,
                      'put("h_fault", bytes_vec(h_fault));',
                      'put("h_faultx", bytes_vec(h_fault));',
                      count=2)
    v = soa_layout.check(ROOT, cpp_text=mutated)
    msgs = [x.message for x in v]
    assert any("'h_faultx'" in m and "never consumed" in m
               for m in msgs), msgs
    assert any("'h_fault'" in m and "never exports" in m
               for m in msgs), msgs


def test_quarantine_flight_kind_drift_is_caught(cpp_text):
    """FR_FAULT_QUARANTINE is the containment plane's attribution
    record: dropping it from the C++ enum must be flagged against the
    trace/events.py twin (fail-closed FR_ namespace)."""
    mutated = _mutate(cpp_text,
                      "FR_FAULT_QUARANTINE, FR_N }",
                      "FR_N }")
    v = twin_constants.check(ROOT, cpp_text=mutated)
    assert any("FR_FAULT_QUARANTINE" in x.message or
               "FR_N" in x.message for x in v), \
        [x.render() for x in v]


def test_ks_enum_drift_is_caught(cpp_text):
    """Device-kernel observatory (ISSUE 15): a drifted stage slot in
    the C++ registry must flag against every twin — trace/events.py
    AND both span kernels, which each pin the slots they occupy."""
    mutated = _mutate(cpp_text, "constexpr int KS_CODEL = 2;",
                      "constexpr int KS_CODEL = 3;")
    v = twin_constants.check(ROOT, cpp_text=mutated)
    msgs = [x.message for x in v]
    assert sum("KS_CODEL" in m for m in msgs) >= 3, msgs


def test_ks_record_size_drift_is_caught(cpp_text):
    """KS_REC grows only with a coordinated trace/events.py struct
    change; a one-sided size bump must fail the pass."""
    mutated = _mutate(cpp_text, "constexpr int KS_REC_BYTES = 224;",
                      "constexpr int KS_REC_BYTES = 232;")
    v = twin_constants.check(ROOT, cpp_text=mutated)
    assert any("KS_REC_BYTES" in x.message for x in v), \
        [x.render() for x in v]


def test_unregistered_ks_constant_fails_closed(cpp_text):
    """A new KS_* stage added to the registry without a contract row
    (and a trace/events.py twin) must fail the pass, not silently
    under-check."""
    mutated = _mutate(cpp_text, "constexpr int KS_REC_BYTES = 224;",
                      "constexpr int KS_REC_BYTES = 224;\n"
                      "constexpr int KS_ROGUE = 99;")
    v = twin_constants.check(ROOT, cpp_text=mutated)
    assert any("KS_ROGUE" in x.message and "no contract row"
               in x.message for x in v), [x.render() for x in v]


def test_ks_stage_name_table_reorder_is_caught(cpp_text):
    """KS_NAMES renders every occupancy table; a reordered entry must
    flag against the trace/events.py string-table twin."""
    mutated = _mutate(cpp_text,
                      '    "pop",\n    "step",\n    "codel",',
                      '    "step",\n    "pop",\n    "codel",')
    v = twin_constants.check(ROOT, cpp_text=mutated)
    assert any("KS_NAMES" in x.message for x in v), \
        [x.render() for x in v]


def test_async_hazard_bites_on_real_dispatch_loop(tmp_path):
    """Pass-3 async-hazard (ISSUE 16), real-tree mutation: an engine
    mutation slipped between the grow loop's raw `_span_call` dispatch
    and its block_until_ready force in ops/phold_span.py must flag —
    the window's basis would drift with no landing check to catch it."""
    from shadow_tpu.analysis import determinism
    path = os.path.join(ROOT, "shadow_tpu", "ops", "phold_span.py")
    with open(path) as fh:
        src = fh.read()
    anchor = ("                    jax.block_until_ready(out)\n"
              "                if fresh_fn:\n")
    mutated = _mutate(
        src, anchor,
        "                    self.engine.run_until(0)\n" + anchor)
    mpath = tmp_path / "phold_span.py"
    mpath.write_text(mutated)
    v = determinism.check(ROOT, paths=[str(mpath)])
    hits = [x for x in v if x.rule == "async-hazard"]
    assert any("run_until" in x.message for x in hits), \
        [x.render() for x in v]
    # the unmutated tree is clean — the in-flight guard publication
    # (_commit_spec) and the forces close every window
    clean = determinism.check(ROOT, paths=[path])
    assert all(x.rule != "async-hazard" for x in clean), \
        [x.render() for x in clean]


def test_memberlist_constant_drift_is_caught(cpp_text):
    # the engine's probe timeout against the device stepper's twin
    mutated = _mutate(cpp_text, "ML_PROBE_TO = 500000000",
                      "ML_PROBE_TO = 500000001")
    v = twin_constants.check(ROOT, cpp_text=mutated)
    assert any("ML_PROBE_TO" in x.message for x in v), \
        [x.render() for x in v]


def test_unregistered_memberlist_enum_fails_closed(cpp_text):
    mutated = _mutate(cpp_text, "MLC_UPD_DROPPED, MLC_N",
                      "MLC_UPD_DROPPED, MLC_EXTRA, MLC_N")
    v = twin_constants.check(ROOT, cpp_text=mutated)
    assert any("MLC_EXTRA" in x.message for x in v), \
        [x.render() for x in v]


def test_memberlist_column_rename_is_caught(cpp_text):
    # ml_export's broadcast-queue column renamed: the codec's read of
    # it becomes a phantom and the export's column dead traffic
    mutated = _mutate(cpp_text, 'put("ml_bq_tx", bytes_vec(bq_tx));',
                      'put("ml_bq_txx", bytes_vec(bq_tx));')
    v = soa_layout.check(ROOT, cpp_text=mutated)
    msgs = [x.message for x in v]
    assert any("ml_bq_txx" in m for m in msgs), msgs
    assert any("'ml_bq_tx'" in m and "[memberlist]" in m for m in msgs), \
        msgs


def test_memberlist_view_dtype_drift_is_caught(cpp_text):
    mutated = _mutate(cpp_text,
                      "std::vector<uint16_t> order(H * N, 0);",
                      "std::vector<uint32_t> order(H * N, 0);")
    v = soa_layout.check(ROOT, cpp_text=mutated)
    assert any("ml_order" in x.message and "[memberlist-view]"
               in x.message for x in v), [x.render() for x in v]
