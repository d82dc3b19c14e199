"""PR 10 API-migration contract: the typed EngineOptions / Telemetry
objects, the one-release deprecation shims over the old boolean kwargs,
and the named EngineOutputs tuple.

Pins:
  * every legacy boolean kwarg (``record_beta``, ``record_watermarks``,
    ``trace``, ``auto_reframe``) warns EXACTLY once per process, keyed
    on the kwarg name — not once per call site;
  * ``interpret`` is no option at all: the backend decides it;
  * ``engine=`` / ``chunk_records=`` migrate silently (they name real
    knobs, not observations);
  * the shimmed spelling and the typed spelling are BIT-identical;
  * wrong types fail loudly (TypeError naming the entry point);
  * ``ChaosCampaign.run`` / ``BittideNetwork.run_scenario`` accept the
    same two objects;
  * ``simulate_ensemble_dense`` returns a named EngineOutputs whose
    positional layout is unchanged (old tuple-unpacking code still runs).
"""
import warnings

import numpy as np
import pytest

from repro._compat import reset_deprecation_warnings
from repro.core import (BittideNetwork, ControllerConfig, SimConfig,
                        fully_connected, make_links)
from repro.kernels import (EngineOptions, EngineOutputs, simulate_ensemble_dense,
                           simulate_fused)
from repro.scenarios import (ChaosCampaign, FreqStep, FreqStepSampler,
                             Scenario, run_scenario)
from repro.telemetry import Telemetry

TOPO = fully_connected(6)
LINKS = make_links(TOPO, cable_m=2.0)
CTRL = ControllerConfig(kp=2e-7)
CFG = SimConfig(dt=1e-3, steps=96, record_every=12)
SC = Scenario(events=(FreqStep(t=0.03, nodes=(0,), delta_ppm=2.0),))


def _ppm(n=6, seed=3):
    ppm = np.random.default_rng(seed).uniform(-0.5, 0.5, n)
    return (ppm - ppm.mean()).astype(np.float32)


def _caught(fn):
    """Run ``fn`` with a re-armed registry; return the DeprecationWarnings."""
    reset_deprecation_warnings()
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        fn()
    return [w for w in rec if issubclass(w.category, DeprecationWarning)]


@pytest.mark.parametrize("kwargs,token", [
    (dict(record_beta=True), "record_beta"),
    (dict(record_watermarks=True), "record_watermarks"),
    (dict(trace=True), "trace"),
    (dict(auto_reframe=True), "auto_reframe"),
])
def test_legacy_kwargs_warn_exactly_once(kwargs, token):
    ppm = _ppm()

    def go():
        run_scenario(TOPO, LINKS, CTRL, ppm, SC, CFG, **kwargs)
        run_scenario(TOPO, LINKS, CTRL, ppm, SC, CFG, **kwargs)  # 2nd call

    got = _caught(go)
    assert len(got) == 1, [str(w.message) for w in got]
    assert token in str(got[0].message)
    assert "Telemetry" in str(got[0].message)


def test_interpret_kwarg_warns_once():
    # Its shim is gone with the option: the backend alone decides whether
    # the kernels run in the interpreter, so every entry point refuses
    # the kwarg outright instead of warning.
    ppm = _ppm()
    with pytest.raises(TypeError, match="interpret"):
        simulate_fused(TOPO, LINKS, ppm, steps=24, kp=2e-7,
                       record_every=12, interpret=True)
    with pytest.raises(TypeError, match="interpret"):
        run_scenario(TOPO, LINKS, CTRL, ppm, SC, CFG, interpret=True)
    with pytest.raises(TypeError, match="interpret"):
        EngineOptions(interpret=True)


def test_engine_and_chunk_kwargs_are_silent():
    ppm = _ppm()
    got = _caught(lambda: run_scenario(TOPO, LINKS, CTRL, ppm, SC, CFG,
                                       engine="fused", chunk_records=2))
    assert got == []


def test_shimmed_and_typed_spellings_bit_identical():
    ppm = _ppm()
    reset_deprecation_warnings()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        old = run_scenario(TOPO, LINKS, CTRL, ppm, SC, CFG,
                           engine="fused", record_beta=True,
                           record_watermarks=True)
    new = run_scenario(TOPO, LINKS, CTRL, ppm, SC, CFG,
                       options=EngineOptions(engine="fused"),
                       telemetry=Telemetry(beta=True, watermarks=True))
    np.testing.assert_array_equal(new.freq_ppm, old.freq_ppm)
    np.testing.assert_array_equal(new.beta, old.beta)
    np.testing.assert_array_equal(new.psi, old.psi)
    assert new.engine == old.engine == "fused"


def test_wrong_types_fail_loudly():
    ppm = _ppm()
    with pytest.raises(TypeError, match="EngineOptions"):
        run_scenario(TOPO, LINKS, CTRL, ppm, SC, CFG, options="fused")
    with pytest.raises(TypeError, match="Telemetry"):
        run_scenario(TOPO, LINKS, CTRL, ppm, SC, CFG, telemetry=True)


def _tiny_campaign(**kw):
    return ChaosCampaign(
        topo=TOPO, ctrl=CTRL, num_draws=3, seed=1, ppm_range=0.05,
        cfg=SimConfig(dt=1e-3, steps=96, record_every=12),
        samplers=(FreqStepSampler(t=0.03, ppm_range=(0.5, 1.5)),), **kw)


def test_chaos_campaign_typed_api():
    camp = _tiny_campaign()
    got = _caught(lambda: camp.run(record_watermarks=True))
    assert len(got) == 1 and "record_watermarks" in str(got[0].message)

    out = camp.run(telemetry=Telemetry(watermarks=True),
                   options=EngineOptions(engine="fused"))
    assert out.result.engine == "fused"
    assert out.result.watermarks is not None
    # The campaign force-records β for triage even though the caller's
    # Telemetry left it off.
    assert out.result.beta.size > 0


def test_network_run_scenario_passthrough():
    net = BittideNetwork(topo=TOPO, links=LINKS, ppm_u=_ppm())
    res = net.run_scenario(SC, ctrl=CTRL, cfg=CFG,
                           options=EngineOptions(engine="tiled"),
                           telemetry=Telemetry(beta=True))
    assert res.engine == "tiled"
    assert res.beta.size > 0
    got = _caught(lambda: net.run_scenario(SC, ctrl=CTRL, cfg=CFG,
                                           engine="tiled", auto_reframe=True))
    assert len(got) == 1 and "auto_reframe" in str(got[0].message)


def test_engine_outputs_named_and_positional():
    # The engine layer's return is a NamedTuple whose leading fields keep
    # the historical (psi, nu, freq, ...) positional layout — code that
    # indexed the old 5-tuple still runs, new code reads names.
    assert EngineOutputs._fields[:5] == ("psi", "nu", "freq", "beta",
                                         "watermarks")
    out = EngineOutputs(psi=1, nu=2, freq=3)
    psi, nu, freq, beta, wm, guard, c_est = out
    assert (psi, nu, freq) == (1, 2, 3)
    assert beta is None and wm is None and guard is None and c_est is None

    # And the public ensemble entry point still unpacks like the
    # historical 2-tuple while exposing the named telemetry fields.
    ppm = np.atleast_2d(_ppm())
    res = simulate_ensemble_dense(TOPO, LINKS, ppm, steps=24, kp=2e-7,
                                  record_every=12,
                                  telemetry=Telemetry(beta=True))
    freq, psi = res
    assert freq.shape == (1, 2, TOPO.num_nodes)
    assert res.beta is not None and res.beta.shape[0] == 1
    assert res.watermarks is None
