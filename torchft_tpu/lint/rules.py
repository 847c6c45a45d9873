"""Rule implementations for the contract linter.

Each rule is a pure function ``(root: str) -> List[Finding]`` over the
extractors in ``extract.py``.  A rule FIRES (returns findings) only on
contract drift; an empty list means the contract holds.  Rules are
registered in ``RULES`` — the report counts a rule class as "active"
when it ran to completion, found drift or not.
"""

from __future__ import annotations

import dataclasses
import os
import re
import subprocess
from typing import Callable, Dict, List, Optional, Set, Tuple

from torchft_tpu.lint import extract as ex

# ----------------------------------------------------------------------


@dataclasses.dataclass
class Finding:
    rule: str
    message: str
    file: str = ""
    line: int = 0

    def format(self) -> str:
        loc = f"{self.file}:{self.line}: " if self.file else ""
        return f"[{self.rule}] {loc}{self.message}"

    def to_json(self) -> Dict[str, object]:
        return dataclasses.asdict(self)


# Contract source locations, relative to the repo root.
CHAOS_PY = "torchft_tpu/chaos.py"
CHAOS_CC = "torchft_tpu/_cpp/chaos.cc"
CHAOS_HPP = "torchft_tpu/_cpp/chaos.hpp"
NATIVE_PY = "torchft_tpu/_native.py"
COLLECTIVES_HPP = "torchft_tpu/_cpp/collectives.hpp"
COORD_PY = "torchft_tpu/coordination.py"
TELEMETRY_PY = "torchft_tpu/telemetry.py"
KNOBS_PY = "torchft_tpu/knobs.py"
LIGHTHOUSE_CC = "torchft_tpu/_cpp/lighthouse.cc"
MANAGER_CC = "torchft_tpu/_cpp/manager_server.cc"
KNOBS_DOC = "docs/KNOBS.md"


def _p(root: str, rel: str) -> str:
    return os.path.join(root, rel)


def _py_files(root: str) -> List[str]:
    """Every Python source the package-wide rules scan: the package and
    the tools dir (tests are exempt — they emit throwaway event kinds
    and poke env vars on purpose)."""
    out: List[str] = []
    for sub in ("torchft_tpu", "tools"):
        base = _p(root, sub)
        for dirpath, _dirnames, filenames in os.walk(base):
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    out.append(os.path.join(dirpath, fn))
    return out


def _rel(root: str, path: str) -> str:
    try:
        return os.path.relpath(path, root)
    except ValueError:
        return path


# ----------------------------------------------------------------------
# 1. golden-constants
# ----------------------------------------------------------------------


def rule_golden_constants(root: str) -> List[Finding]:
    R = "golden-constants"
    out: List[Finding] = []
    py = ex.py_hash_constants(_p(root, CHAOS_PY))
    cc = ex.cc_hash_constants(_p(root, CHAOS_CC))
    for fn in ex.HASH_FUNCS:
        p, c = py.get(fn, {}), cc.get(fn, {})
        if p.get("missing"):
            out.append(Finding(R, f"{fn}() missing", CHAOS_PY))
            continue
        if c.get("missing"):
            out.append(Finding(R, f"{fn}() missing", CHAOS_CC))
            continue
        if p["big_ints"] != c["big_ints"]:
            only_py = {hex(v) for v in p["big_ints"] - c["big_ints"]}
            only_cc = {hex(v) for v in c["big_ints"] - p["big_ints"]}
            out.append(
                Finding(
                    R,
                    f"{fn}(): golden constants drifted "
                    f"(py-only={sorted(only_py)} cc-only={sorted(only_cc)})",
                    CHAOS_CC,
                )
            )
        if p["shifts"] != c["shifts"]:
            out.append(
                Finding(
                    R,
                    f"{fn}(): shift amounts drifted "
                    f"(py={p['shifts']} cc={c['shifts']})",
                    CHAOS_CC,
                )
            )
    pu = ex.py_hash_unit(_p(root, CHAOS_PY))
    cu = ex.cc_hash_unit(_p(root, CHAOS_CC))
    if pu["shift"] is None or pu["divisor"] is None:
        out.append(Finding(R, "_hash_unit() not extractable", CHAOS_PY))
    elif cu["shift"] is None:
        out.append(
            Finding(R, "unit-float expression not found", CHAOS_CC)
        )
    else:
        if (pu["shift"], pu["divisor"]) != (cu["shift"], cu["divisor"]):
            out.append(
                Finding(
                    R,
                    "hash-unit drifted: "
                    f"py >>({pu['shift']})/{pu['divisor']} vs "
                    f"cc >>({cu['shift']})/{cu['divisor']}",
                    CHAOS_CC,
                )
            )
    sent_py = ex.py_step_sentinel(_p(root, CHAOS_PY))
    sent_cc = ex.cc_step_sentinel(_p(root, CHAOS_CC))
    if sent_cc is None:
        out.append(Finding(R, "kStepMax not found", CHAOS_CC))
    elif sent_cc not in sent_py:
        out.append(
            Finding(
                R,
                f"step sentinel drifted: cc kStepMax=2^{sent_cc.bit_length() - 1}"
                f" not among py sentinels {sorted(v.bit_length() - 1 for v in sent_py)}",
                CHAOS_CC,
            )
        )
    return out


# ----------------------------------------------------------------------
# 2. chaos-enums
# ----------------------------------------------------------------------


def rule_chaos_enums(root: str) -> List[Finding]:
    R = "chaos-enums"
    out: List[Finding] = []
    kinds_py = ex.py_tuple_of_strings(_p(root, CHAOS_PY), "KINDS")
    planes_py = ex.py_tuple_of_strings(_p(root, CHAOS_PY), "PLANES")
    kinds_cc = ex.cc_kind_names(_p(root, CHAOS_CC))
    planes_cc = ex.cc_planes(_p(root, CHAOS_CC))
    nkinds_cc = ex.cc_num_kinds(_p(root, CHAOS_CC))
    if kinds_py is None:
        out.append(Finding(R, "KINDS tuple not found", CHAOS_PY))
    if kinds_cc is None:
        out.append(Finding(R, "kKindNames[] not found", CHAOS_CC))
    if kinds_py and kinds_cc and kinds_py != kinds_cc:
        out.append(
            Finding(
                R,
                f"fault kinds drifted (ordered): py={list(kinds_py)} "
                f"cc={list(kinds_cc)}",
                CHAOS_CC,
            )
        )
    if kinds_cc and nkinds_cc is not None and nkinds_cc != len(kinds_cc):
        out.append(
            Finding(
                R,
                f"kNumKinds={nkinds_cc} but kKindNames has "
                f"{len(kinds_cc)} entries",
                CHAOS_CC,
            )
        )
    if planes_py is None:
        out.append(Finding(R, "PLANES tuple not found", CHAOS_PY))
    if planes_cc is None:
        out.append(Finding(R, "valid_plane() not found", CHAOS_CC))
    if planes_py and planes_cc and set(planes_py) != set(planes_cc):
        out.append(
            Finding(
                R,
                f"planes drifted: py={sorted(planes_py)} "
                f"cc={sorted(planes_cc)}",
                CHAOS_CC,
            )
        )
    enum = ex.hpp_kind_enum(_p(root, CHAOS_HPP))
    if enum is None:
        out.append(Finding(R, "enum class Kind not found", CHAOS_HPP))
    elif kinds_py:
        expected = [ex.kind_to_enum_name(k) for k in kinds_py]
        names = [n for n, _v in enum]
        if names != expected:
            out.append(
                Finding(
                    R,
                    f"Kind enum names drifted: hpp={names} "
                    f"expected={expected}",
                    CHAOS_HPP,
                )
            )
        for i, (n, v) in enumerate(enum):
            if v is not None and v != i:
                out.append(
                    Finding(
                        R,
                        f"Kind enum {n}={v} breaks the positional "
                        f"contract (expected {i})",
                        CHAOS_HPP,
                    )
                )
    return out


# ----------------------------------------------------------------------
# 3. chaos-grammar
# ----------------------------------------------------------------------


def rule_chaos_grammar(root: str) -> List[Finding]:
    R = "chaos-grammar"
    out: List[Finding] = []
    py = ex.py_grammar_params(_p(root, CHAOS_PY))
    cc = ex.cc_grammar_params(_p(root, CHAOS_CC))
    if not py:
        out.append(
            Finding(R, "parse_rule param ladder not found", CHAOS_PY)
        )
    if not cc:
        out.append(
            Finding(R, "parse_rule param ladder not found", CHAOS_CC)
        )
    if py and cc and py != cc:
        out.append(
            Finding(
                R,
                f"grammar param keys drifted: py-only={sorted(py - cc)} "
                f"cc-only={sorted(cc - py)}",
                CHAOS_CC,
            )
        )
    return out


# ----------------------------------------------------------------------
# 4. c-abi
# ----------------------------------------------------------------------


def rule_c_abi(root: str) -> List[Finding]:
    R = "c-abi"
    out: List[Finding] = []
    py = ex.py_abi(_p(root, NATIVE_PY))
    cc: Dict[str, Dict[str, object]] = {}
    cc.update(ex.cc_abi(_p(root, COLLECTIVES_HPP)))
    cc.update(ex.cc_abi(_p(root, CHAOS_HPP)))
    if not py:
        out.append(Finding(R, "_declare() not extractable", NATIVE_PY))
        return out
    if not cc:
        out.append(
            Finding(R, 'extern "C" block not found', COLLECTIVES_HPP)
        )
        return out
    for fn in sorted(set(py) - set(cc)):
        out.append(
            Finding(
                R,
                f"{fn} declared in _declare() but missing from the "
                'extern "C" headers',
                NATIVE_PY,
            )
        )
    for fn in sorted(set(cc) - set(py)):
        out.append(
            Finding(
                R,
                f'{fn} exported by extern "C" but not declared in '
                "_declare() (ctypes would guess int-returning varargs)",
                COLLECTIVES_HPP,
            )
        )
    for fn in sorted(set(py) & set(cc)):
        p, c = py[fn], cc[fn]
        if p.get("nargs") != c.get("nargs"):
            out.append(
                Finding(
                    R,
                    f"{fn}: argtypes arity {p.get('nargs')} != header "
                    f"arity {c.get('nargs')}",
                    NATIVE_PY,
                )
            )
        if p.get("void") != c.get("void"):
            out.append(
                Finding(
                    R,
                    f"{fn}: restype void-ness {p.get('void')} != header "
                    f"{c.get('void')}",
                    NATIVE_PY,
                )
            )
    dt_py = ex.py_dtype_codes(_p(root, NATIVE_PY))
    dt_cc = ex.cc_dtype_codes(_p(root, COLLECTIVES_HPP))
    if dt_py is None:
        out.append(Finding(R, "DTYPE_CODES not found", NATIVE_PY))
    elif dt_py != dt_cc:
        out.append(
            Finding(
                R,
                f"dtype codes drifted: py={dt_py} cc={dt_cc}",
                NATIVE_PY,
            )
        )
    op_py = ex.py_op_codes(_p(root, NATIVE_PY))
    op_cc = ex.cc_op_codes(_p(root, COLLECTIVES_HPP))
    if op_py is None:
        out.append(Finding(R, "OP_* codes not found", NATIVE_PY))
    elif op_py != op_cc:
        out.append(
            Finding(
                R, f"op codes drifted: py={op_py} cc={op_cc}", NATIVE_PY
            )
        )
    return out


# ----------------------------------------------------------------------
# 5. rpc-methods
# ----------------------------------------------------------------------

_CLIENT_SERVER = {
    "LighthouseClient": LIGHTHOUSE_CC,
    "ManagerClient": MANAGER_CC,
}


def rule_rpc_methods(root: str) -> List[Finding]:
    R = "rpc-methods"
    out: List[Finding] = []
    clients = ex.py_rpc_clients(_p(root, COORD_PY))
    disp = {
        rel: ex.cc_dispatch_types(_p(root, rel))
        for rel in (LIGHTHOUSE_CC, MANAGER_CC)
    }
    sent_cc = {
        rel: ex.cc_sent_types(_p(root, rel))
        for rel in (LIGHTHOUSE_CC, MANAGER_CC)
    }
    for cls, server in _CLIENT_SERVER.items():
        if cls not in clients:
            out.append(Finding(R, f"client class {cls} not found",
                               COORD_PY))
            continue
        for t in sorted(clients[cls]["types"] - disp[server]):
            out.append(
                Finding(
                    R,
                    f'{cls} sends type "{t}" but {server} never '
                    f"dispatches it",
                    COORD_PY,
                )
            )
    # C++-originated requests (heartbeats, quorum forwards, drain fan-out)
    # must land on a dispatched type of SOME server.
    all_disp = disp[LIGHTHOUSE_CC] | disp[MANAGER_CC]
    for rel, types in sent_cc.items():
        for t in sorted(types - all_disp):
            out.append(
                Finding(
                    R,
                    f'{rel} originates type "{t}" but no server '
                    f"dispatches it",
                    rel,
                )
            )
    # Reverse direction: a dispatched type nobody can send is dead
    # protocol surface (or a renamed sender).
    py_types: Set[str] = set()
    for cls in clients:
        py_types |= clients[cls]["types"]
    all_sent = py_types | sent_cc[LIGHTHOUSE_CC] | sent_cc[MANAGER_CC]
    for rel in (LIGHTHOUSE_CC, MANAGER_CC):
        for t in sorted(disp[rel] - all_sent):
            out.append(
                Finding(
                    R,
                    f'{rel} dispatches type "{t}" but no client or '
                    f"server ever sends it",
                    rel,
                )
            )
    return out


# ----------------------------------------------------------------------
# 6. rpc-keys
# ----------------------------------------------------------------------


def rule_rpc_keys(root: str) -> List[Finding]:
    R = "rpc-keys"
    out: List[Finding] = []
    clients = ex.py_rpc_clients(_p(root, COORD_PY))
    lh_keys = clients.get("LighthouseClient", {}).get("keys", set())
    mgr_keys = clients.get("ManagerClient", {}).get("keys", set())
    member_json = ex.py_method_dict_keys(
        _p(root, COORD_PY), "QuorumMember.to_json"
    )
    # Keys a server reads from requests must be sendable by its clients:
    # the Python client class, or the other C++ server's request builders.
    reads_lh = ex.cc_req_keys(_p(root, LIGHTHOUSE_CC))
    senders_lh = (
        lh_keys
        | ex.cc_assigned_keys(_p(root, MANAGER_CC))
        | ex.cc_assigned_keys(_p(root, LIGHTHOUSE_CC))  # self HTTP fwd
    )
    for k in sorted(reads_lh - senders_lh):
        out.append(
            Finding(
                R,
                f'lighthouse reads request key "{k}" that no sender '
                f"includes",
                LIGHTHOUSE_CC,
            )
        )
    reads_mgr = ex.cc_req_keys(_p(root, MANAGER_CC))
    senders_mgr = mgr_keys | ex.cc_assigned_keys(_p(root, LIGHTHOUSE_CC))
    for k in sorted(reads_mgr - senders_mgr):
        out.append(
            Finding(
                R,
                f'manager server reads request key "{k}" that no '
                f"sender includes",
                MANAGER_CC,
            )
        )
    # Quorum-member parse keys come from QuorumMember.to_json.
    member_cc = ex.cc_member_keys(_p(root, LIGHTHOUSE_CC))
    for k in sorted(member_cc - member_json):
        out.append(
            Finding(
                R,
                f'lighthouse parses member key "{k}" absent from '
                f"QuorumMember.to_json()",
                LIGHTHOUSE_CC,
            )
        )
    # PR-5 heartbeat digest: wire keys + the ≤512 B budget fields.
    wire = ex.py_method_dict_keys(
        _p(root, TELEMETRY_PY), "StepDigest.to_wire"
    )
    if not wire:
        out.append(
            Finding(R, "StepDigest.to_wire() not found", TELEMETRY_PY)
        )
    digest_cc = ex.cc_digest_keys(_p(root, LIGHTHOUSE_CC))
    for k in sorted(digest_cc - wire):
        out.append(
            Finding(
                R,
                f'lighthouse reads digest key "{k}" absent from '
                f"StepDigest.to_wire()",
                LIGHTHOUSE_CC,
            )
        )
    budget = ex.py_class_int_attr(
        _p(root, TELEMETRY_PY), "StepDigest", "MAX_WIRE_BYTES"
    )
    if budget != 512:
        out.append(
            Finding(
                R,
                f"StepDigest.MAX_WIRE_BYTES={budget} != 512 (the "
                f"heartbeat-budget contract in docs/FAULT_MODEL.md)",
                TELEMETRY_PY,
            )
        )
    peers = ex.py_class_int_attr(
        _p(root, TELEMETRY_PY), "StepDigest", "MAX_PEERS"
    )
    if peers != 8:
        out.append(
            Finding(
                R,
                f"StepDigest.MAX_PEERS={peers} != 8 (bw map cap that "
                f"keeps the digest inside the budget)",
                TELEMETRY_PY,
            )
        )
    return out


# ----------------------------------------------------------------------
# 7. event-kind-registry
# ----------------------------------------------------------------------


def rule_event_kinds(root: str) -> List[Finding]:
    R = "event-kind-registry"
    out: List[Finding] = []
    registry = ex.py_event_kinds_registry(_p(root, TELEMETRY_PY))
    if registry is None:
        out.append(
            Finding(R, "EVENT_KINDS registry not found", TELEMETRY_PY)
        )
        return out
    emitted = ex.py_emitted_kinds(_py_files(root))
    for kind in sorted(set(emitted) - set(registry)):
        path, line = emitted[kind][0]
        out.append(
            Finding(
                R,
                f'journal event kind "{kind}" is emitted but not '
                f"registered in telemetry.EVENT_KINDS",
                _rel(root, path),
                line,
            )
        )
    for kind in sorted(set(registry) - set(emitted)):
        out.append(
            Finding(
                R,
                f'EVENT_KINDS entry "{kind}" is never emitted '
                f"(dead registry entry or renamed call site)",
                TELEMETRY_PY,
            )
        )
    return out


# ----------------------------------------------------------------------
# 8. env-knob-registry
# ----------------------------------------------------------------------


def rule_env_knobs(root: str) -> List[Finding]:
    R = "env-knob-registry"
    out: List[Finding] = []
    knobs_path = _p(root, KNOBS_PY)
    registry = ex.py_knob_registry(knobs_path)
    if registry is None:
        out.append(Finding(R, "knob registry not found", KNOBS_PY))
        return out
    py_files = [
        f
        for f in _py_files(root)
        if os.path.abspath(f) != os.path.abspath(knobs_path)
    ]
    for path, line, name in ex.py_raw_env_reads(py_files):
        out.append(
            Finding(
                R,
                f"raw os.environ read of {name}: go through "
                f"torchft_tpu.knobs accessors",
                _rel(root, path),
                line,
            )
        )
    accessed: Set[str] = set()
    for path, line, name in ex.py_knob_accessor_calls(_py_files(root)):
        accessed.add(name)
        if name not in registry:
            out.append(
                Finding(
                    R,
                    f"knobs accessor call names unregistered knob "
                    f"{name}",
                    _rel(root, path),
                    line,
                )
            )
    cc_files: List[str] = []
    cpp_dir = _p(root, "torchft_tpu/_cpp")
    if os.path.isdir(cpp_dir):
        for fn in sorted(os.listdir(cpp_dir)):
            if fn.endswith((".cc", ".hpp", ".h")):
                cc_files.append(os.path.join(cpp_dir, fn))
    cc_reads = ex.cc_env_reads(cc_files)
    for name in sorted(cc_reads):
        scope = registry.get(name, {}).get("scope")
        if scope is None:
            out.append(
                Finding(
                    R,
                    f"C++ getenv({name}) is unregistered — add it to "
                    f"knobs.py with scope 'cpp' or 'both'",
                    KNOBS_PY,
                )
            )
        elif scope not in ("cpp", "both"):
            out.append(
                Finding(
                    R,
                    f"{name} is read by C++ but registered with scope "
                    f"'{scope}'",
                    KNOBS_PY,
                )
            )
    for name, meta in sorted(registry.items()):
        scope = meta["scope"]
        if scope in ("py", "both") and name not in accessed:
            out.append(
                Finding(
                    R,
                    f"{name} is registered (scope '{scope}') but never "
                    f"read via knobs accessors — dead knob or missed "
                    f"migration",
                    KNOBS_PY,
                )
            )
        if scope in ("cpp", "both") and name not in cc_reads:
            out.append(
                Finding(
                    R,
                    f"{name} is registered with scope '{scope}' but no "
                    f"C++ getenv reads it",
                    KNOBS_PY,
                )
            )
    # docs/KNOBS.md must match the generated form byte-for-byte.
    doc_path = _p(root, KNOBS_DOC)
    gen = _generated_knob_doc(knobs_path)
    if gen is None:
        out.append(
            Finding(R, "could not load knobs.py to generate docs",
                    KNOBS_PY)
        )
    elif not os.path.exists(doc_path):
        out.append(
            Finding(
                R,
                "docs/KNOBS.md missing — run "
                "`python tools/tft_lint.py --gen-knob-docs`",
                KNOBS_DOC,
            )
        )
    else:
        have = open(doc_path).read()
        if have.strip() != gen.strip():
            out.append(
                Finding(
                    R,
                    "docs/KNOBS.md is stale — regenerate with "
                    "`python tools/tft_lint.py --gen-knob-docs`",
                    KNOBS_DOC,
                )
            )
    return out


def _generated_knob_doc(knobs_path: str) -> Optional[str]:
    """Loads ``knobs.py`` from the tree under lint (not the installed
    package — fixture trees in tests carry their own registry) and
    returns ``generate_doc()``."""
    import importlib.util
    import sys

    try:
        spec = importlib.util.spec_from_file_location(
            "_tft_lint_knobs", knobs_path
        )
        assert spec is not None and spec.loader is not None
        mod = importlib.util.module_from_spec(spec)
        # dataclass field introspection resolves annotations through
        # sys.modules[cls.__module__]; register before exec.
        sys.modules["_tft_lint_knobs"] = mod
        try:
            spec.loader.exec_module(mod)
            return mod.generate_doc()
        finally:
            sys.modules.pop("_tft_lint_knobs", None)
    except Exception:
        return None


# ----------------------------------------------------------------------
# 9. wallclock-free-chaos
# ----------------------------------------------------------------------


def rule_wallclock_free(root: str) -> List[Finding]:
    R = "wallclock-free-chaos"
    out: List[Finding] = []
    for func, line, call in ex.py_wallclock_calls(_p(root, CHAOS_PY)):
        if call == "<function missing>":
            out.append(
                Finding(
                    R,
                    f"decision-path function {func} not found",
                    CHAOS_PY,
                )
            )
        else:
            out.append(
                Finding(
                    R,
                    f"{func}() calls {call} — the chaos decision path "
                    f"must be wall-clock/RNG free for seeded replay",
                    CHAOS_PY,
                    line,
                )
            )
    return out


# ----------------------------------------------------------------------
# 10. artifact-hygiene
# ----------------------------------------------------------------------

_ARTIFACT_SUFFIXES = (".o", ".so", ".a", ".d")


def rule_artifact_hygiene(root: str) -> List[Finding]:
    R = "artifact-hygiene"
    out: List[Finding] = []
    if not os.path.isdir(_p(root, ".git")):
        return out  # fixture tree: nothing tracked to police
    try:
        tracked = subprocess.run(
            ["git", "-C", root, "ls-files"],
            capture_output=True,
            text=True,
            timeout=30,
            check=True,
        ).stdout.splitlines()
    except Exception as e:  # git missing/broken: report, don't crash
        return [Finding(R, f"git ls-files failed: {e}", ".git")]
    for path in tracked:
        if path.startswith("torchft_tpu/_cpp/bin/") or path.endswith(
            _ARTIFACT_SUFFIXES
        ):
            out.append(
                Finding(
                    R,
                    f"build artifact tracked in git: {path} (the lint "
                    f"pass scans sources only; make rebuilds bin/)",
                    path,
                )
            )
    gi_path = _p(root, ".gitignore")
    if os.path.exists(gi_path):
        gi = open(gi_path).read()
        if "torchft_tpu/_cpp/bin" not in gi:
            out.append(
                Finding(
                    R,
                    ".gitignore does not exclude torchft_tpu/_cpp/bin/",
                    ".gitignore",
                )
            )
    return out


# ----------------------------------------------------------------------
# fleet-keys: the /fleet.json payload contract.
#
# The lighthouse builds the fleet snapshot in C++ (fleet_snapshot /
# fleet_agg_locked); obs_top.py and obs_export.py consume it in Python.
# The golden sets below ARE the contract: the C++ builder must write
# exactly these keys, and every key the Python consumers read at the
# fleet/row/agg level must be one the builder writes.

FLEET_TOP_KEYS = {
    "ts_ms", "gen", "snap_ms", "replicas", "agg", "anomalies",
    "anomaly_seq",
    # Namespace plane: every payload names its job island; the composite
    # (unfiltered) payload adds per-job summary rollups and the root
    # lighthouse's district table.
    "job", "jobs", "districts",
    # Failure-evidence plane: the island's signal ring, its monotone seq
    # cursor, and per-source totals.
    "signals", "signal_seq", "signal_counts",
    # Goodput plane: the SLO burn-rate rise-edge ring + its seq cursor.
    "slo_burns", "slo_seq",
}
FLEET_ROW_KEYS = {
    "last_hb_age_ms", "hb_interval_ms", "digest", "digest_age_ms",
    "flags", "straggler",
    # Last failure signal naming this replica as subject (null if none).
    "signal", "signal_age_ms",
}
FLEET_AGG_KEYS = {
    "n", "n_digest", "stragglers", "median_rate", "median_step",
    "median_goodput", "max_commit_failures", "anomalies_dropped",
    "quorum_world", "joins_total", "leaves_total", "epoch",
    "signals_dropped",
    # Goodput plane: per-kind badput sums (closed BADPUT_KINDS object, or
    # null before any acct digest), the job goodput fraction, MTBF/ETTR
    # from the evidence plane, and the SLO evaluator state.
    "badput_s", "goodput_frac", "mtbf_s", "ettr_s", "slo_burning",
    "slo_dropped",
}

# Consumer read sites: variable name -> which key level it addresses.
# obs_top/obs_export bind `fleet` to the parsed payload, `agg` to
# fleet["agg"], and iterate rows as `r` or index `replicas[rid]`.
_FLEET_READ_PATTERNS: List[Tuple[str, str]] = [
    (r"\bfleet\.get\(\s*(['\"])([^'\"]+)\1", "top"),
    (r"\bagg\.get\(\s*(['\"])([^'\"]+)\1", "agg"),
    (r"\br\.get\(\s*(['\"])([^'\"]+)\1", "row"),
    (r"\breplicas\[rid\]\.get\(\s*(['\"])([^'\"]+)\1", "row"),
]
_FLEET_CONSUMERS = ("tools/obs_top.py", "tools/obs_export.py")


def rule_fleet_keys(root: str) -> List[Finding]:
    R = "fleet-keys"
    out: List[Finding] = []
    cc_path = _p(root, LIGHTHOUSE_CC)
    if not os.path.exists(cc_path):
        return out  # fixture tree without the C++ plane
    text = ex.strip_cc_comments(open(cc_path).read())

    def assigned(body: str, var: str) -> Set[str]:
        return set(re.findall(rf'\b{var}\["([^"]+)"\]\s*=', body))

    snap = ex.cc_function_body(text, "fleet_snapshot")
    agg_fn = ex.cc_function_body(text, "fleet_agg_locked")
    if not snap or not agg_fn:
        return [
            Finding(
                R,
                "could not extract fleet_snapshot/fleet_agg_locked "
                "bodies from lighthouse.cc",
                LIGHTHOUSE_CC,
            )
        ]
    produced = {
        "top": assigned(snap, "f"),
        "row": assigned(snap, "r"),
        "agg": assigned(agg_fn, "agg"),
    }
    golden = {
        "top": FLEET_TOP_KEYS,
        "row": FLEET_ROW_KEYS,
        "agg": FLEET_AGG_KEYS,
    }
    for level in ("top", "row", "agg"):
        for k in sorted(produced[level] - golden[level]):
            out.append(
                Finding(
                    R,
                    f"lighthouse writes undeclared fleet.json {level} "
                    f"key {k!r} (add it to the golden set and teach "
                    f"the consumers)",
                    LIGHTHOUSE_CC,
                )
            )
        for k in sorted(golden[level] - produced[level]):
            out.append(
                Finding(
                    R,
                    f"declared fleet.json {level} key {k!r} is no "
                    f"longer written by fleet_snapshot/fleet_agg_locked",
                    LIGHTHOUSE_CC,
                )
            )

    # Consumers may read a subset, but never a key the builder does
    # not produce (a typo'd .get() silently reads None forever).
    for rel in _FLEET_CONSUMERS:
        path = _p(root, rel)
        if not os.path.exists(path):
            continue
        src = open(path).read()
        for pat, level in _FLEET_READ_PATTERNS:
            for _q, key in re.findall(pat, src):
                if key not in golden[level]:
                    out.append(
                        Finding(
                            R,
                            f"reads fleet.json {level} key {key!r} "
                            f"that the lighthouse never writes",
                            rel,
                        )
                    )
    return out


# ----------------------------------------------------------------------
# signal-sources: the failure-evidence plane's source enum.
#
# telemetry.SIGNAL_SOURCES (python emitters, detect/report tooling) and
# lighthouse.cc kSignalSourceNames (the ingest filter) must agree
# POSITIONALLY — the lighthouse silently drops signals whose source it
# does not know, so a drifted entry loses evidence with no error anywhere.


def rule_signal_sources(root: str) -> List[Finding]:
    R = "signal-sources"
    out: List[Finding] = []
    cc_path = _p(root, LIGHTHOUSE_CC)
    if not os.path.exists(cc_path):
        return out  # fixture tree without the C++ plane
    py = ex.py_tuple_of_strings(_p(root, TELEMETRY_PY), "SIGNAL_SOURCES")
    cc = ex.cc_string_array(cc_path, "kSignalSourceNames")
    if py is None:
        out.append(Finding(R, "SIGNAL_SOURCES tuple not found", TELEMETRY_PY))
    if cc is None:
        out.append(Finding(R, "kSignalSourceNames[] not found", LIGHTHOUSE_CC))
    if py and cc and py != cc:
        out.append(
            Finding(
                R,
                f"signal sources drifted (ordered): py={list(py)} "
                f"cc={list(cc)}",
                LIGHTHOUSE_CC,
            )
        )
    # Every source a python emitter uses must be declared. Emit sites all
    # funnel through journal events / the "signal" RPC with a literal
    # source string: catch the literals.
    if py:
        emitters = (
            "torchft_tpu/manager.py",
            "torchft_tpu/coordination.py",
            "torchft_tpu/orchestration/runner.py",
        )
        pat = re.compile(
            r"(?:source\s*=\s*|_signal\(\s*|\.signal\(\s*)(['\"])([a-z_]+)\1"
        )
        for rel in emitters:
            path = _p(root, rel)
            if not os.path.exists(path):
                continue
            src = open(path).read()
            for _q, source in pat.findall(src):
                if source not in py:
                    out.append(
                        Finding(
                            R,
                            f"emits undeclared signal source {source!r} "
                            f"(the lighthouse will drop it): add it to "
                            f"SIGNAL_SOURCES + kSignalSourceNames",
                            rel,
                        )
                    )
    return out


# ----------------------------------------------------------------------
# badput-kinds: the time-accounting plane's closed classification.
#
# telemetry.BADPUT_KINDS (the ledger + the digest's positional "acct"
# array) and lighthouse.cc kBadputKindNames (the aggregation index) must
# agree POSITIONALLY — a drifted entry silently mis-bills seconds to the
# wrong kind on one side with no error anywhere. FAULT_BADPUT_KINDS (the
# headline goodput-retention numerator) must stay a subset.


def rule_badput_kinds(root: str) -> List[Finding]:
    R = "badput-kinds"
    out: List[Finding] = []
    py = ex.py_tuple_of_strings(_p(root, TELEMETRY_PY), "BADPUT_KINDS")
    if py is None:
        out.append(Finding(R, "BADPUT_KINDS tuple not found", TELEMETRY_PY))
        return out
    cc_path = _p(root, LIGHTHOUSE_CC)
    if os.path.exists(cc_path):
        cc = ex.cc_string_array(cc_path, "kBadputKindNames")
        if cc is None:
            out.append(
                Finding(R, "kBadputKindNames[] not found", LIGHTHOUSE_CC)
            )
        elif py != cc:
            out.append(
                Finding(
                    R,
                    f"badput kinds drifted (ordered): py={list(py)} "
                    f"cc={list(cc)}",
                    LIGHTHOUSE_CC,
                )
            )
    fault = ex.py_tuple_of_strings(
        _p(root, TELEMETRY_PY), "FAULT_BADPUT_KINDS"
    )
    if fault is None:
        out.append(
            Finding(R, "FAULT_BADPUT_KINDS tuple not found", TELEMETRY_PY)
        )
    else:
        for k in fault:
            if k not in py:
                out.append(
                    Finding(
                        R,
                        f"FAULT_BADPUT_KINDS entry {k!r} is not a "
                        f"declared BADPUT_KINDS member",
                        TELEMETRY_PY,
                    )
                )
    return out


# ----------------------------------------------------------------------

RULES: List[Tuple[str, Callable[[str], List[Finding]]]] = [
    ("golden-constants", rule_golden_constants),
    ("chaos-enums", rule_chaos_enums),
    ("chaos-grammar", rule_chaos_grammar),
    ("c-abi", rule_c_abi),
    ("rpc-methods", rule_rpc_methods),
    ("rpc-keys", rule_rpc_keys),
    ("event-kind-registry", rule_event_kinds),
    ("env-knob-registry", rule_env_knobs),
    ("wallclock-free-chaos", rule_wallclock_free),
    ("artifact-hygiene", rule_artifact_hygiene),
    ("fleet-keys", rule_fleet_keys),
    ("signal-sources", rule_signal_sources),
    ("badput-kinds", rule_badput_kinds),
]


def run_all(
    root: str, only: Optional[Set[str]] = None
) -> Tuple[List[Finding], List[str]]:
    """Runs every rule against the tree at ``root``.  Returns
    ``(findings, rule names that ran)``.  A rule that crashes reports
    itself as a finding rather than killing the run — a linter that
    dies on a parse error hides every other contract."""
    findings: List[Finding] = []
    ran: List[str] = []
    for name, fn in RULES:
        if only is not None and name not in only:
            continue
        try:
            findings.extend(fn(root))
        except Exception as e:
            findings.append(
                Finding(name, f"rule crashed: {type(e).__name__}: {e}")
            )
        ran.append(name)
    return findings, ran
