"""Pass 2: SoA layout contract check.

For every device-span family the C++ engine exports a dict of packed
column bytes and later imports the Python codec's dict back.  Four
schemas must stay in lockstep:

    span_export_*  (C++ writer)  ==  _to_arrays    (Python reader)
    span_import_*  (C++ reader)  ==  _from_arrays  (Python writer)

and the C++ reader's per-column dtype must match the C++ writer's.
Any unread exported column (dead device-link traffic), phantom read
(KeyError at runtime), or dtype skew (silent reinterpretation of raw
bytes!) is flagged here, statically, instead of surfacing as a span
abort or a byte-mismatch at runtime.

To register a new device-span family: add a row to FAMILIES naming the
C++ export/import functions and the Python codec module; the codec
must expose `_to_arrays` / `_from_arrays` methods using the
`np.frombuffer(d[key], dtype)` / `out[key] = ...` idioms (a row may
name other methods: `to_method` / `from_method`).  A module that
serves several rows keeps one set of RESIDENT_* tables: its rows'
state columns are classified together, by the row with `residency`
(the others say `"residency": False`).
"""

from __future__ import annotations

import os

from shadow_tpu.analysis import cpp_extract, py_extract
from shadow_tpu.analysis.report import Violation

CPP = "native/netplane.cpp"

FAMILIES = [
    {
        "name": "phold",
        "export_fn": "eng_span_export_phold",
        "import_fn": "eng_span_import_phold",
        "codec": "shadow_tpu/ops/phold_span.py",
        # extraction-sanity floor: fewer keys than this means the
        # extractor lost the function, not that the schema shrank
        "min_columns": 60,
    },
    {
        "name": "tcp",
        "export_fn": "eng_span_export_tcp",
        "import_fn": "eng_span_import_tcp",
        "codec": "shadow_tpu/ops/tcp_span.py",
        "min_columns": 120,
    },
    # memberlist (family 2 of the phold rows): the members' SWIM state
    # rides span_export_phold through ml_export / ml_import, their
    # dense views through calls of their own
    {
        "name": "memberlist",
        "export_fn": "ml_export",
        "import_fn": "ml_import",
        "codec": "shadow_tpu/ops/memberlist_span.py",
        "min_columns": 50,
        "state_methods": ("_to_arrays", "_view_to_arrays"),
    },
    {
        "name": "memberlist-view",
        "export_fn": "eng_span_export_ml_view",
        "import_fn": "eng_span_import_ml_view",
        "codec": "shadow_tpu/ops/memberlist_span.py",
        "to_method": "_view_to_arrays",
        "from_method": "_view_from_arrays",
        "min_columns": 3,
        "residency": False,
    },
]


def check(repo_root: str, cpp_text: str | None = None) -> list:
    if cpp_text is None:
        with open(os.path.join(repo_root, CPP)) as fh:
            cpp_text = fh.read()

    violations: list[Violation] = []
    for fam in FAMILIES:
        name = fam["name"]
        codec = fam["codec"]
        codec_path = os.path.join(repo_root, codec)
        try:
            exported = cpp_extract.extract_export_layout(
                cpp_text, fam["export_fn"])
            imported = cpp_extract.extract_import_layout(
                cpp_text, fam["import_fn"])
        except KeyError as exc:
            violations.append(Violation(
                "soa-layout", CPP, f"[{name}] {exc.args[0]}"))
            continue
        to_m = fam.get("to_method", "_to_arrays")
        from_m = fam.get("from_method", "_from_arrays")
        consumed, unres_c = py_extract.extract_consumed_schema(
            codec_path, to_m)
        produced, unres_p = py_extract.extract_produced_keys(
            codec_path, from_m)

        if len(exported) < fam["min_columns"]:
            violations.append(Violation(
                "soa-layout", CPP,
                f"[{name}] export extractor found only {len(exported)} "
                f"columns (< {fam['min_columns']}); unrecognized idiom?"))
        for line, what in unres_c + unres_p:
            violations.append(Violation(
                "soa-layout", codec,
                f"[{name}] unresolvable {what} (the contract cannot "
                f"see this read/write)", line=line))

        # export -> _to_arrays
        for key in sorted(set(exported) - set(consumed)):
            violations.append(Violation(
                "soa-layout", CPP,
                f"[{name}] exported column {key!r} is never consumed "
                f"by {codec} _to_arrays (dead device-link traffic)"))
        for key in sorted(set(consumed) - set(exported)):
            violations.append(Violation(
                "soa-layout", codec,
                f"[{name}] _to_arrays reads column {key!r} that "
                f"{fam['export_fn']} never exports (KeyError at span "
                f"time)"))
        for key in sorted(set(exported) & set(consumed)):
            if consumed[key] is not None and consumed[key] != exported[key]:
                violations.append(Violation(
                    "soa-layout", codec,
                    f"[{name}] column {key!r} decoded as "
                    f"{consumed[key]} but exported as {exported[key]} "
                    f"(byte reinterpretation)"))

        # _from_arrays -> import
        for key in sorted(set(imported) - set(produced)):
            violations.append(Violation(
                "soa-layout", codec,
                f"[{name}] {fam['import_fn']} requires column {key!r} "
                f"that _from_arrays never produces (import failure)"))
        for key in sorted(set(produced) - set(imported)):
            violations.append(Violation(
                "soa-layout", codec,
                f"[{name}] _from_arrays produces column {key!r} that "
                f"{fam['import_fn']} never reads (dead device-link "
                f"traffic)"))

        # C++ import dtype vs C++ export dtype (same byte layout end
        # to end; only meaningful for columns both sides touch)
        for key in sorted(set(imported) & set(exported)):
            if imported[key] != exported[key]:
                violations.append(Violation(
                    "soa-layout", CPP,
                    f"[{name}] column {key!r} exported as "
                    f"{exported[key]} but imported as {imported[key]}"))

        # Residency classification (the dirty-column export protocol,
        # ISSUE 3): every SoA state column the codec materializes must
        # be classified CARRIED / STATIC / DERIVED in the module's
        # RESIDENT_* tables — a column added to the export without a
        # classification entry would otherwise be reused across
        # device-resident spans with unreviewed dirtiness semantics.
        if not fam.get("residency", True):
            continue
        state_keys, unres_s = set(), []
        for method in fam.get("state_methods", ("_to_arrays",)):
            keys, unres = py_extract.extract_state_keys(codec_path,
                                                        method)
            state_keys |= keys
            unres_s += unres
        for line, what in unres_s:
            violations.append(Violation(
                "soa-layout", codec,
                f"[{name}] unresolvable {what} (the residency "
                f"classification cannot see this column)", line=line))
        sets_ = py_extract.extract_residency_sets(codec_path)
        missing_tables = [t for t in ("RESIDENT_STATIC",
                                      "RESIDENT_DERIVED",
                                      "RESIDENT_CARRIED")
                          if t not in sets_]
        if missing_tables:
            violations.append(Violation(
                "soa-layout", codec,
                f"[{name}] residency table(s) missing/unparseable: "
                f"{', '.join(missing_tables)}"))
        else:
            r_static = sets_["RESIDENT_STATIC"]
            r_derived = sets_["RESIDENT_DERIVED"]
            r_carried = sets_["RESIDENT_CARRIED"]
            for a, b in (("STATIC", "DERIVED"), ("STATIC", "CARRIED"),
                         ("DERIVED", "CARRIED")):
                dup = sets_[f"RESIDENT_{a}"] & sets_[f"RESIDENT_{b}"]
                if dup:
                    violations.append(Violation(
                        "soa-layout", codec,
                        f"[{name}] column(s) {sorted(dup)} in both "
                        f"RESIDENT_{a} and RESIDENT_{b}"))
            public = {k for k in state_keys if not k.startswith("_")}
            for key in sorted(public - r_static - r_derived
                              - r_carried):
                violations.append(Violation(
                    "soa-layout", codec,
                    f"[{name}] state column {key!r} has no residency "
                    f"class (dirty-column protocol): add it to "
                    f"RESIDENT_CARRIED / _STATIC / _DERIVED"))
            # DERIVED entries may be kernel-side registers the codec
            # never materializes; STATIC/CARRIED must exist.
            for key in sorted((r_static | r_carried) - public):
                violations.append(Violation(
                    "soa-layout", codec,
                    f"[{name}] residency entry {key!r} names a column "
                    f"the codec no longer produces (stale entry)"))
    return violations
