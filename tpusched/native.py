"""Loader/wrapper for the native wire decoder (native/fastdecode.cc).

The C++ extension replicates snapshot_from_proto + SnapshotBuilder.build
end to end (same interning, same bucketing, same arrays — fuzz-tested
for exact equality in tests/test_native.py) but runs ~10x faster on
large snapshots, which matters because decode — not the TPU solve — is
the sidecar's serving bottleneck at 10k x 5k (SURVEY.md §7 hard part 6).

Build-on-demand: the .so is compiled with g++ on first use and cached
next to this file under a name keyed by the SHA-256 of
native/fastdecode.cc (atomic rename; lock-guarded), so a build from any
other source — a stale file copied along with the tree, say — is never
loaded. No pybind11 — plain
CPython C API + numpy headers. Everything degrades gracefully to the
Python decoder when a compiler is unavailable, and codec.decode_snapshot
falls back to the Python path on any native decode error.

Known divergence from Python float() parsing: non-ASCII numerals in
label values (e.g. Arabic-Indic digits) parse via Python but yield NaN
natively — they silently change Gt/Lt matching on such labels only.
ASCII literals, underscores, inf/nan (any case) all match exactly.
"""

from __future__ import annotations

import dataclasses
import hashlib
import importlib.util
import os
import subprocess
import sys
import sysconfig

import numpy as np

from tpusched.config import Buckets, EngineConfig
from tpusched.snapshot import (
    AtomTable,
    ClusterSnapshot,
    NodeArrays,
    PodArrays,
    RunningPodArrays,
    SigTable,
    SnapshotMeta,
)

_SRC = os.path.join(os.path.dirname(os.path.dirname(__file__)), "native",
                    "fastdecode.cc")

_mod = None
_build_failed: str | None = None
_load_lock = __import__("threading").Lock()


def _so_path() -> str:
    """Where the build of the committed source lives."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(os.path.dirname(__file__),
                        f"_fastdecode-{digest}.so")


def _build_so(so: str) -> None:
    # Compile to a private temp path and os.replace into place: g++
    # writes -o non-atomically, and concurrent first-callers (the
    # sidecar's thread pool, or a server and a bench sharing the
    # checkout) must never dlopen a half-written file.
    tmp = f"{so}.build-{os.getpid()}"
    cmd = [
        "g++", "-O2", "-shared", "-fPIC", "-std=c++17",
        f"-I{sysconfig.get_paths()['include']}",
        f"-I{np.get_include()}",
        _SRC, "-o", tmp,
    ]
    proc = subprocess.run(cmd, capture_output=True, text=True)
    if proc.returncode != 0:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise RuntimeError(f"native build failed:\n{proc.stderr[-2000:]}")
    os.replace(tmp, so)


def _load():
    global _mod, _build_failed
    if _mod is not None:
        return _mod
    with _load_lock:
        if _mod is not None:
            return _mod
        if _build_failed is not None:
            raise RuntimeError(_build_failed)
        try:
            so = _so_path()  # tpl: disable=TPL102(one-time native-module init; _load_lock exists precisely to serialize it, the source hash included)
            if not os.path.exists(so):
                _build_so(so)
            spec = importlib.util.spec_from_file_location("_fastdecode", so)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)  # tpl: disable=TPL003(one-time native-module load; _load_lock exists precisely to serialize this init)
            _mod = mod
            return mod
        except Exception as e:  # remember: retrying every call would be slow
            _build_failed = f"tpusched native decoder unavailable: {e}"
            raise RuntimeError(_build_failed) from e


def available() -> bool:
    try:
        _load()
        return True
    except RuntimeError:
        return False


def decode_snapshot_bytes(
    raw: bytes,
    config: EngineConfig | None = None,
    buckets: Buckets | None = None,
) -> tuple[ClusterSnapshot, SnapshotMeta]:
    """Native decode of a serialized tpusched.ClusterSnapshot. Exact
    drop-in for codec.snapshot_from_proto(msg.SerializeToString(), ...)."""
    config = config or EngineConfig()
    mod = _load()
    bdict = dataclasses.asdict(buckets) if buckets is not None else None
    d = mod.decode_snapshot(raw, tuple(config.resources), bdict)
    snap = ClusterSnapshot(
        nodes=NodeArrays(
            allocatable=d["node_allocatable"], used=d["node_used"],
            label_pairs=d["node_label_pairs"], label_keys=d["node_label_keys"],
            label_nums=d["node_label_nums"], taint_ids=d["node_taint_ids"],
            domain=d["node_domain"], schedulable=d["node_schedulable"],
            valid=d["node_valid"],
        ),
        pods=PodArrays(
            requests=d["pod_requests"], base_priority=d["pod_base_priority"],
            slo_target=d["pod_slo_target"],
            observed_avail=d["pod_observed_avail"],
            tolerated=d["pod_tolerated"], label_pairs=d["pod_label_pairs"],
            label_keys=d["pod_label_keys"],
            req_term_atoms=d["pod_req_term_atoms"],
            req_term_valid=d["pod_req_term_valid"],
            pref_term_atoms=d["pod_pref_term_atoms"],
            pref_term_valid=d["pod_pref_term_valid"],
            pref_weight=d["pod_pref_weight"],
            ts_key=d["pod_ts_key"], ts_max_skew=d["pod_ts_max_skew"],
            ts_when=d["pod_ts_when"], ts_sel_atoms=d["pod_ts_sel_atoms"],
            ts_sig=d["pod_ts_sig"], ts_valid=d["pod_ts_valid"],
            ia_key=d["pod_ia_key"], ia_sel_atoms=d["pod_ia_sel_atoms"],
            ia_sig=d["pod_ia_sig"], ia_anti=d["pod_ia_anti"],
            ia_required=d["pod_ia_required"], ia_weight=d["pod_ia_weight"],
            ia_valid=d["pod_ia_valid"], group=d["pod_group"],
            namespace=d["pod_namespace"],
            tolerates_unsched=d["pod_tolerates_unsched"],
            valid=d["pod_valid"],
        ),
        running=RunningPodArrays(
            node_idx=d["run_node_idx"], requests=d["run_requests"],
            priority=d["run_priority"], slack=d["run_slack"],
            label_pairs=d["run_label_pairs"], label_keys=d["run_label_keys"],
            anti_sig=d["run_anti_sig"], namespace=d["run_namespace"],
            pdb_group=d["run_pdb_group"], valid=d["run_valid"],
        ),
        atoms=AtomTable(
            key=d["atom_key"], op=d["atom_op"], pairs=d["atom_pairs"],
            num=d["atom_num"], valid=d["atom_valid"],
        ),
        sigs=SigTable(
            key=d["sig_key"], atoms=d["sig_atoms"], ns=d["sig_ns"],
            ns_all=d["sig_ns_all"], valid=d["sig_valid"],
        ),
        taint_effect=d["taint_effect"],
        group_min_member=d["group_min_member"],
        pdb_allowed=d["pdb_allowed"],
    )
    meta = SnapshotMeta(
        node_names=d["node_names"], pod_names=d["pod_names"],
        n_nodes=d["n_nodes"], n_pods=d["n_pods"], n_running=d["n_running"],
        buckets=Buckets(**d["buckets"]),
        group_names=d["group_names"],
        running_names=d["running_names"],
    )
    return snap, meta
