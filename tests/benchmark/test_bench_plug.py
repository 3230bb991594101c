"""A configuration brings its own generator and reference, named by
path: a test-only parity configuration whose cluster carries a required
hostname anti-affinity (antiaffinity_cluster.py, antiaffinity_reference.py,
beside this file) runs end to end through the harness, with its own
buckets and warm-up trees, and no harness file knows of it. A
configuration whose modules are missing or lack their protocol fails
with a clear error before any cycle runs."""

import os
import types

import pytest

pytest.importorskip("grpc")

import benchtiny  # noqa: E402
import control  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402

BASE = "k8s5k-parity.backlog"
HERE = "tests/benchmark"
BUCKETS = dict(atoms=4, atom_values=1, term_atoms=1, topo_keys=1,
               affinity_terms=1, signatures=4, sig_namespaces=1)


def plug_spec(**config) -> dict:
    spec = benchtiny.tiny_spec(BASE)
    spec["config"].update(
        name="k8s5k-antiaffinity-parity",
        generator=f"{HERE}/antiaffinity_cluster.py",
        reference=f"{HERE}/antiaffinity_reference.py",
        buckets=BUCKETS)
    spec["config"].update(config)
    spec["traffic"]["warm_trees"] = ["nodes", "pods", "running", "atoms",
                                     "sigs"]
    return spec


def test_plugged_configuration_is_correct(monkeypatch):
    spec = plug_spec()
    bk = harness.cell_buckets(spec)
    assert {k: getattr(bk, k) for k in BUCKETS} == BUCKETS
    out, diag = benchtiny.run_tiny(BASE, monkeypatch, spec=spec)
    assert out["correct"] is True, out["checks"]
    assert list(out["checks"]) == ["failed_cycles", "malformed_replies",
                                   "pods_differing"]
    win = diag["window"]
    assert win["compiles_in_window"] == 0
    assert win["device_state_rebuilds"] == 0
    assert win["session_hits"] == win["delta_sends"] == out["attempted"] > 0


@pytest.mark.parametrize("case", ["altered_answer", "base_reference"])
def test_plugged_configuration_fails_what_it_must(case, monkeypatch):
    if case == "altered_answer":
        spec, faults = plug_spec(), control.altered_answer
    else:
        # The program keeps the anti-affinity the generator sends: the
        # unconstrained reference disagrees with it.
        spec, faults = plug_spec(reference="benchmark/reference.py"), None
    out, _ = benchtiny.run_tiny(BASE, monkeypatch, spec=spec, faults=faults)
    assert out["correct"] is False, out["checks"]
    assert out["checks"]["pods_differing"]["value"] > 0


def test_plugged_reference_keeps_db_pods_apart():
    spec = plug_spec()
    gen, ref = harness.plugins(spec)
    c = gen.Cluster(spec["config"], spec["traffic"], benchtiny.SEED)
    c.add_pending(int(spec["traffic"]["backlog"]))
    static, rec = c.static_view(), c.cycle_view()
    node, pos = ref.sequential(spec["config"]["engine"], static, rec)
    db = rec["pend"]["db"] & (node >= 0)
    assert db.any()
    assert (rec["db_on_node"][node[db]] == 0).all()
    assert len(set(node[db])) == db.sum()
    base, base_pos = reference.sequential(spec["config"]["engine"], static,
                                          rec)
    assert (pos == base_pos).all() and (node != base).any()


def _run_until_refused(spec, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(harness.CACHE_DIR))
    monkeypatch.setenv("TPU_LOG_DIR", "disabled")
    phases = []
    with pytest.raises(harness.PluginError) as err:
        harness.run_cell(spec, benchtiny.SEED, 1.0, False, require_tpu=False,
                         say=lambda **kw: phases.append(kw["phase"]))
    assert set(phases) <= {"device", "jax_ready"}     # no cycle ran
    return str(err.value)


@pytest.mark.parametrize("config, says", [
    (dict(generator=f"{HERE}/no_such_cluster.py"), "no_such_cluster.py"),
    (dict(reference=f"{HERE}/no_such_reference.py"), "no_such_reference.py"),
    (dict(generator=os.__file__), "not a file in the repo"),
    (dict(generator="benchmark/reference.py"), "lacks Cluster"),
    (dict(reference="benchmark/workload.py"), "lacks sequential"),
    (dict(guarantee="valid"), "lacks VALIDITY_AUDITS"),
], ids=["missing_generator", "missing_reference", "outside_the_repo",
        "generator_without_cluster", "reference_without_sequential",
        "valid_without_audits"])
def test_configuration_without_its_protocol_is_refused(config, says,
                                                       monkeypatch):
    assert says in _run_until_refused(plug_spec(**config), monkeypatch)


def test_configuration_naming_no_modules_is_refused(monkeypatch):
    spec = benchtiny.tiny_spec(BASE)
    del spec["config"]["generator"], spec["config"]["reference"]
    assert "names no generator and no reference" in _run_until_refused(
        spec, monkeypatch)


def test_cluster_without_its_attributes_is_refused(monkeypatch):
    plugins = harness.plugins

    def lacking(spec):
        gen, ref = plugins(spec)

        class Cluster(gen.Cluster):
            def __init__(self, *args):
                super().__init__(*args)
                del self.pend_slot

        return types.SimpleNamespace(Cluster=Cluster), ref

    monkeypatch.setattr(harness, "plugins", lacking)
    assert "lacks pend_slot" in _run_until_refused(plug_spec(), monkeypatch)
