"""Tier-1 gates for the perf ratchet (tools/bench_ratchet.py).

Three contracts, cheap enough for every CI run:

- every committed ledger parses and schema-validates (a truncated or
  hand-mangled ledger is an exit-2 CI error, not a silent green);
- the committed RATCHET.json still passes against the committed ledgers
  (re-blessing and ledger updates travel together);
- the seeded-regression fixture (tests/fixtures/ratchet_regression —
  the hierarchical merge's inter-host byte count inflated past its 5%
  band) makes the ratchet exit 1, so the CI red path is itself tested.

None of these run the benches — the smoke replay (``--smoke``) is the
CI job's own leg.
"""

import copy
import json
import os

from tools import bench_ratchet as br

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(REPO, "tests", "fixtures", "ratchet_regression")
FIXTURE_MULTI = os.path.join(
    REPO, "tests", "fixtures", "ratchet_regression_multi"
)
FIXTURE_POD = os.path.join(
    REPO, "tests", "fixtures", "ratchet_regression_pod"
)


class TestLedgerSchemas:
    def test_committed_ledgers_validate(self):
        ledgers, errors = br.load_ledgers(REPO)
        assert errors == []
        assert set(ledgers) == set(br.LEDGER_SCHEMAS)  # every schema found one

    def test_missing_key_is_an_error(self):
        obj = json.load(open(os.path.join(REPO, "PREDICT_BENCH.json")))
        del obj["cold_start"]
        errs = br.validate_ledger("PREDICT_BENCH.json", obj)
        assert any("cold_start" in e for e in errs)

    def test_bool_does_not_satisfy_numeric_field(self):
        # bool is an int subclass — a ledger field that must be a number
        # (a gate compares against it) rejects True/False explicitly
        obj = json.load(open(os.path.join(REPO, "INGEST_BENCH.json")))
        obj["value"] = True
        errs = br.validate_ledger("INGEST_BENCH.json", obj)
        assert any("value" in e for e in errs)

    def test_fixture_ledgers_validate(self):
        # the regression fixture must fail on the GATE, never on schema
        _, errors = br.load_ledgers(FIXTURE)
        assert errors == []


class TestRatchet:
    def test_committed_ledgers_pass_committed_ratchet(self):
        assert br.main([]) == 0

    def test_seeded_regression_exits_nonzero(self):
        assert br.main(["--ledger-dir", FIXTURE]) == 1

    def test_regression_is_the_comms_bytes_gate(self):
        ledgers, _ = br.load_ledgers(FIXTURE)
        with open(br.ratchet_path(FIXTURE)) as f:
            ratchet = json.load(f)
        bad = [r["id"] for r in br.evaluate(ledgers, ratchet)
               if not r["ok"] and r["enforced"]]
        assert bad == ["comms.inter_host_bytes"]

    def test_update_is_idempotent_against_committed_ledgers(self):
        # RATCHET.json was produced by --update from these exact ledgers;
        # re-deriving must reproduce it byte-for-byte (modulo the file
        # write), or the committed bounds have silently drifted
        ledgers, errors = br.load_ledgers(REPO)
        assert errors == []
        derived = br.derive_ratchet(ledgers)
        with open(os.path.join(REPO, "RATCHET.json")) as f:
            committed = json.load(f)
        assert derived == committed

    def test_every_enforced_gate_has_a_ratchet_entry(self):
        with open(os.path.join(REPO, "RATCHET.json")) as f:
            ratchet = json.load(f)
        assert set(ratchet["gates"]) == {g["id"] for g in br.GATES}

    def test_band_tightens_not_loosens(self):
        # a <= gate's bound sits ABOVE the blessed value and a >= gate's
        # BELOW it — the band is headroom for machine noise, never a
        # hidden relaxation direction flip
        with open(os.path.join(REPO, "RATCHET.json")) as f:
            gates = json.load(f)["gates"]
        for g in br.GATES:
            entry = gates[g["id"]]
            if g["op"] == "<=":
                assert entry["bound"] >= entry["blessed"]
            elif g["op"] == ">=":
                mb = br._min_bound_for(g, entry["backend"])
                assert entry["bound"] <= max(
                    entry["blessed"],
                    entry["blessed"] if mb is None else mb,
                )

    def test_multi_regression_fixture_validates(self):
        # the stacked-training regression fixture must fail on the
        # GATE, never on schema
        _, errors = br.load_ledgers(FIXTURE_MULTI)
        assert errors == []

    def test_multi_speedup_regression_exits_nonzero(self):
        assert br.main(["--ledger-dir", FIXTURE_MULTI]) == 1

    def test_multi_regression_is_the_speedup_gate(self):
        # the fixture regresses ONLY the K=64 stacked speedup (below the
        # hard per-backend floor); every other gate stays green
        ledgers, _ = br.load_ledgers(FIXTURE_MULTI)
        with open(br.ratchet_path(FIXTURE_MULTI)) as f:
            ratchet = json.load(f)
        bad = [r["id"] for r in br.evaluate(ledgers, ratchet)
               if not r["ok"] and r["enforced"]]
        assert bad == ["multi.speedup_k64"]

    def test_pod_regression_fixture_validates(self):
        # the pod-rehearsal regression fixture must fail on the GATE,
        # never on schema
        _, errors = br.load_ledgers(FIXTURE_POD)
        assert errors == []

    def test_pod_scaling_regression_exits_nonzero(self):
        assert br.main(["--ledger-dir", FIXTURE_POD]) == 1

    def test_pod_regression_is_the_scaling_gate(self):
        # the fixture records scaling.gate_enforced=true (an accelerator
        # topology) with two_proc below the 1.7x floor; evaluate() must
        # re-resolve enforcement from the ledger under evaluation — not
        # the cpu blessing — and fail EXACTLY pod.scaling_2proc
        ledgers, _ = br.load_ledgers(FIXTURE_POD)
        with open(br.ratchet_path(FIXTURE_POD)) as f:
            ratchet = json.load(f)
        bad = [r["id"] for r in br.evaluate(ledgers, ratchet)
               if not r["ok"] and r["enforced"]]
        assert bad == ["pod.scaling_2proc"]

    def test_pod_scaling_advisory_on_cpu_never_fails(self):
        # the committed cpu ledger records gate_enforced=false (every
        # process shares the host core, the ratio is physically capped);
        # regressing two_proc there must stay an advisory failure
        ledgers, _ = br.load_ledgers(REPO)
        ledgers = copy.deepcopy(ledgers)
        ledgers["BENCH_POD.json"]["scaling"]["two_proc"] = 0.1
        with open(os.path.join(REPO, "RATCHET.json")) as f:
            ratchet = json.load(f)
        results = br.evaluate(ledgers, ratchet)
        bad = [r for r in results if r["id"] == "pod.scaling_2proc"][0]
        assert not bad["ok"] and not bad["enforced"]

    def test_max_bound_resolves_per_backend(self):
        gate = {"max_bound": {"cpu": 3.61, "*": 1.0}}
        assert br._max_bound_for(gate, "cpu") == 3.61
        assert br._max_bound_for(gate, "tpu") == 1.0
        assert br._max_bound_for({"max_bound": 2.0}, "cpu") == 2.0
        assert br._max_bound_for({}, "cpu") is None

    def test_max_bound_caps_the_blessing(self):
        # the ingest trend gate pins the pre-pipeline 3.61 s record as
        # the worst value --update may ever legitimize: a blessing far
        # above it derives a bound clamped to exactly the ceiling
        gate = [g for g in br.GATES if g["id"] == "ingest.steady_trend"][0]
        assert gate["op"] == "<="
        xb = br._max_bound_for(gate, "cpu")
        assert xb is not None
        led = {
            gate["ledger"]: json.load(
                open(os.path.join(REPO, gate["ledger"]))
            )
        }
        # inflate the steady value well past the ceiling
        led[gate["ledger"]]["value"] = xb * 10
        derived = br.derive_ratchet(led)
        assert derived["gates"]["ingest.steady_trend"]["bound"] == xb

    def test_min_bound_resolves_per_backend(self):
        gate = {"min_bound": {"cpu": 2.0, "*": 5.0}}
        assert br._min_bound_for(gate, "cpu") == 2.0
        assert br._min_bound_for(gate, "tpu") == 5.0
        assert br._min_bound_for({"min_bound": 10.0}, "cpu") == 10.0
        assert br._min_bound_for({}, "cpu") is None

    def test_advisory_gate_never_fails_the_run(self):
        # ingest.steady_s is advisory while the ledger records
        # gate_enforced=false — regress it past the band and the run
        # stays green with the gate listed as an advisory failure
        ledgers, _ = br.load_ledgers(REPO)
        ledgers = copy.deepcopy(ledgers)
        ledgers["INGEST_BENCH.json"]["value"] = 99.0
        with open(os.path.join(REPO, "RATCHET.json")) as f:
            ratchet = json.load(f)
        assert ratchet["gates"]["ingest.steady_s"]["enforced"] is False
        results = br.evaluate(ledgers, ratchet)
        bad = [r for r in results if r["id"] == "ingest.steady_s"][0]
        assert not bad["ok"] and not bad["enforced"]
