"""Constant-time certifier tests (repro.analysis; DESIGN.md §11).

Two directions of proof:

* every *registered* engine datapath certifies clean (the real contract),
  and the paper-faithful chain baseline passes only through its explicit,
  reasoned waiver — never silently;
* *seeded violations* — a data-dependent ``while_loop``, an f64 leak, a
  quadratic unroll, a host callback, an in-trace transfer — each trip
  exactly the invariant built to catch them, and the same seeded engine
  makes the CLI exit nonzero.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.certify import (
    EngineContract,
    certify_all,
    certify_callable,
)
from repro.analysis.lint import lint_paths, lint_source
from repro.analysis.markers import constant_time_waiver, waivers_of
from repro.analysis.report import FAIL, PASS, SKIPPED, WAIVED

#: tiny contract for fixture traces — invariants don't care about scale
SMALL = EngineContract(batch=8, capacity=64, block_rows=8)


def _check(report, invariant):
    (res,) = [c for c in report.checks if c.invariant == invariant]
    return res


def _tracer(fn, *operands):
    """omega -> closed jaxpr of ``fn(*operands, omega)``."""
    return lambda om: jax.make_jaxpr(lambda *a: fn(*a, om))(*operands)


KEYS8 = np.arange(8, dtype=np.uint32)


# ---------------------------------------------------------------------------
# seeded violations — each trips exactly its invariant
# ---------------------------------------------------------------------------


def _while_route(keys, omega):
    """Trip count depends on key VALUES — the storm-cliff bug class."""

    def cond(carry):
        k, _ = carry
        return jnp.any(k > 0)

    def body(carry):
        k, i = carry
        return (k >> 1).astype(jnp.uint32), i + np.uint32(1)

    _, steps = jax.lax.while_loop(
        cond, body, (keys.astype(jnp.uint32), np.uint32(0))
    )
    return jnp.full(keys.shape, steps.astype(jnp.int32))


def test_data_dependent_while_fails_certification():
    report = certify_callable(
        "fixture", "route/jnp", _tracer(_while_route, KEYS8), contract=SMALL
    )
    res = _check(report, "while-free")
    assert res.status == FAIL
    assert "while" in res.detail
    assert not report.ok


def test_waiver_downgrades_while_to_waived_with_reason():
    report = certify_callable(
        "fixture",
        "route/jnp",
        _tracer(_while_route, KEYS8),
        contract=SMALL,
        waivers={"while-free": "fixture: bounded by construction"},
    )
    res = _check(report, "while-free")
    assert res.status == WAIVED
    assert res.waiver == "fixture: bounded by construction"
    assert report.ok  # waived is not failed...
    assert report.to_dict()["while-free"]["waiver"]  # ...but never silent


def _f64_route(keys, omega):
    """Accumulates in float64 — breaks the u32-limb dtype closure."""
    acc = keys.astype(jnp.float64)
    for _ in range(omega):
        acc = acc * 1.0000001 + 1.0
    return acc.astype(jnp.int32)


def test_f64_leak_fails_dtype_closed():
    report = certify_callable(
        "fixture", "route/jnp", _tracer(_f64_route, KEYS8), contract=SMALL
    )
    res = _check(report, "dtype-closed")
    assert res.status == FAIL
    assert "float64" in res.detail


def _quadratic_route(keys, omega):
    """O(ω²) ops — unroll depth is NOT the declared ω."""
    out = keys.astype(jnp.uint32)
    for i in range(omega):
        for _ in range(i + 1):
            out = out + np.uint32(1)
    return out.astype(jnp.int32)


def test_quadratic_unroll_fails_affine():
    report = certify_callable(
        "fixture", "route/jnp", _tracer(_quadratic_route, KEYS8), contract=SMALL
    )
    assert _check(report, "unroll-affine").status == FAIL


def _callback_route(keys, omega):
    jax.debug.print("routing {n} keys", n=keys.shape[0])
    return keys.astype(jnp.int32)


def test_host_callback_fails():
    report = certify_callable(
        "fixture",
        "route/jnp",
        _tracer(_callback_route, KEYS8),
        contract=SMALL,
        check_affine=False,
    )
    assert _check(report, "callback-free").status == FAIL


def _transfer_route(keys, omega):
    # a traced operand: JAX folds a device_put of a numpy constant into a
    # closed-over constant, which leaves no device_put equation to count
    lut = jax.device_put(jnp.arange(8, dtype=jnp.int32))
    return lut[keys.astype(jnp.int32) % 8]


def test_in_trace_device_put_fails_transfer_count():
    report = certify_callable(
        "fixture",
        "route/jnp",
        _tracer(_transfer_route, KEYS8),
        contract=SMALL,
        check_affine=False,
    )
    res = _check(report, "transfer-count")
    assert res.status == FAIL
    assert "1 device_put" in res.detail


# ---------------------------------------------------------------------------
# the real contract: every registered engine certifies clean
# ---------------------------------------------------------------------------


def test_every_registered_engine_certifies():
    from repro.core.registry import BULK_ENGINES

    report = certify_all()
    assert report.ok, report.render()
    by_engine = {}
    for t in report.targets:
        by_engine.setdefault(t.engine, set()).add(t.target)
    # jnp mirror AND pallas kernel certified for every datapath of every entry
    for name in BULK_ENGINES:
        assert by_engine[name] >= {
            "route/jnp", "ingest/jnp", "lookup_dyn/jnp",
            "route/pallas", "ingest/pallas", "lookup_dyn/pallas",
        }


def test_chain_baseline_passes_only_via_waiver():
    report = certify_all(engines=[])
    (chain,) = [t for t in report.targets if t.target == "chain/memento_remap"]
    res = _check(chain, "while-free")
    assert res.status == WAIVED
    assert "max_chain" in res.waiver
    assert _check(chain, "unroll-affine").status == SKIPPED
    # remove the waiver and the same trace goes red — the marker is
    # load-bearing, not decorative
    from repro.analysis.certify import certify_chain_baseline
    from repro.core import memento_jax

    unmarked = certify_callable(
        "binomial",
        "chain/memento_remap",
        lambda om: jax.make_jaxpr(
            lambda k, b, m, n, f: memento_jax.memento_remap(k, b, m, n, f)
        )(
            KEYS8,
            np.zeros(8, np.int32),
            np.zeros(64, bool),
            np.uint32(8),
            np.uint32(0),
        ),
        contract=SMALL,
        waivers={},
        check_affine=False,
    )
    assert _check(unmarked, "while-free").status == FAIL
    assert certify_chain_baseline().ok


# ---------------------------------------------------------------------------
# waiver markers
# ---------------------------------------------------------------------------


def test_waiver_requires_reason():
    with pytest.raises(ValueError, match="reason"):
        constant_time_waiver("")(lambda: None)


def test_waivers_seen_through_jit_wrapping():
    @jax.jit
    @constant_time_waiver("test: bounded", invariant="while-free")
    def fn(x):
        return x

    assert waivers_of(fn) == {"while-free": "test: bounded"}
    assert waivers_of(lambda: None) == {}


# ---------------------------------------------------------------------------
# AST lint (layer 2)
# ---------------------------------------------------------------------------


def _rules(findings):
    return {f.rule for f in findings}


def test_lint_flags_host_sync_in_hot_function():
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def route(keys):\n"
        "    n = keys.item()\n"
        "    return keys\n"
    )
    findings = lint_source(src)
    assert _rules(findings) == {"host-sync"}
    assert findings[0].line == 4


def test_lint_waiver_comment_suppresses():
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def route(n):\n"
        "    l = (n - 1).bit_length()  # ct: host-ok — n is static\n"
        "    return l\n"
    )
    assert lint_source(src) == []


def test_lint_ignores_cold_functions():
    src = "def oracle(key):\n    return int(key) & 0xFFFFFFFF\n"
    assert lint_source(src) == []


def test_lint_flags_bare_wide_literal_in_limb_arithmetic():
    src = (
        "def _mix_body(x):\n"
        "    return x * 0x9E3779B97F4A7C15\n"
    )
    findings = lint_source(src)
    assert _rules(findings) == {"bare-int"}


def test_lint_accepts_cast_wrapped_literal():
    src = (
        "import numpy as np\n"
        "def _mix_body(x):\n"
        "    return x * np.uint32(0x9E3779B9) + np.uint32(0xFFFFFFFF & 1)\n"
    )
    assert lint_source(src) == []


def test_lint_flags_config_mutation():
    src = "import jax\njax.config.update('jax_enable_x64', True)\n"
    findings = lint_source(src)
    assert _rules(findings) == {"config-mutation"}


def test_repo_hot_paths_lint_clean():
    assert lint_paths() == []


# ---------------------------------------------------------------------------
# HLO gate (layer 3) + strict trip-count recovery
# ---------------------------------------------------------------------------


def test_trip_count_recovery_counted_vs_data_dependent():
    from repro.roofline.hlo_parse import parse_module, while_trip_counts

    def counted(x):
        return jax.lax.fori_loop(0, 1000, lambda i, c: c * 1.0001 + 1.0, x)

    def datadep(x):
        return jax.lax.while_loop(lambda c: c < 100.0, lambda c: c * 1.1 + 1.0, x)

    comps, _ = parse_module(jax.jit(counted).lower(np.float32(2.0)).compile().as_text())
    [(_, _, trips)] = while_trip_counts(comps)
    assert trips == 1000
    comps, _ = parse_module(jax.jit(datadep).lower(np.float32(2.0)).compile().as_text())
    [(_, _, trips)] = while_trip_counts(comps)
    assert trips is None  # unbounded: the gate must not invent a count


def test_hlo_gate_binomial_severity_flat():
    from repro.analysis.hlo_gate import gate_engine

    result = gate_engine("binomial", batch=512)
    assert result.ok, [c.detail for c in result.checks]
    assert _check(result, "hlo-severity-flat").status == PASS
    assert result.op_count > 0


# ---------------------------------------------------------------------------
# CLI: exit 0 on the repo, nonzero on a seeded-violation engine
# ---------------------------------------------------------------------------


def test_cli_certifies_registered_engine(capsys):
    from repro.analysis.__main__ import main

    assert main(["--engine", "jump", "--skip-hlo", "--skip-lint"]) == 0
    out = capsys.readouterr().out
    assert "verdict: CERTIFIED" in out


def test_cli_fails_on_seeded_violation_engine(capsys, monkeypatch):
    from repro.analysis.__main__ import main
    from repro.core import registry

    def bad_route(keys, packed, table, state, omega=16, *, n_words):
        del packed, table, state, n_words
        return _while_route(keys, omega)

    broken = dataclasses.replace(
        registry.BULK_ENGINES["binomial"],
        name="broken",
        route=bad_route,
        ingest=None,
        route_pallas=None,
        ingest_pallas=None,
        lookup_dyn=None,
        lookup_dyn_pallas=None,
    )
    monkeypatch.setitem(registry.BULK_ENGINES, "broken", broken)
    assert (
        main(
            ["--engine", "broken", "--skip-hlo", "--skip-lint",
             "--no-chain-baseline"]
        )
        == 1
    )
    assert "verdict: FAILED" in capsys.readouterr().out


def test_cli_writes_structured_report(tmp_path, capsys):
    import json

    from repro.analysis.__main__ import main

    out = tmp_path / "ct.json"
    assert (
        main(
            ["--engine", "jump", "--skip-hlo", "--skip-lint",
             "--no-chain-baseline", "--report", str(out), "--json"]
        )
        == 0
    )
    data = json.loads(out.read_text())
    assert data["ok"] is True
    assert "while-free" in data["engines"]["jump"]["route/jnp"]
    assert json.loads(capsys.readouterr().out) == data
