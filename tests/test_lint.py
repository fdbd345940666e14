"""guberlint tier-1 gate: the full rule set over gubernator_tpu/ +
tools/ must be clean against the committed baseline, and every rule
must demonstrably fire on its violation fixture.

Deliberately jax-free: the linter is pure-AST (GL000 imports only the
jax-free metrics module), so this file must never pull jax in on its
own — test_linter_is_stdlib_only pins that with a `python -S`
subprocess.
"""

import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.abspath(os.path.join(HERE, os.pardir))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools.lint import (  # noqa: E402
    DEFAULT_BASELINE,
    REGISTRY,
    load_baseline,
    run_lint,
)

FIXTURES = os.path.join(HERE, "lint_fixtures", "gubernator_tpu")


def fixture(*parts):
    return os.path.relpath(os.path.join(FIXTURES, *parts), REPO)


# ---------------------------------------------------------------------------
# repo-wide gate


def test_repo_clean_with_committed_baseline():
    res = run_lint(baseline=load_baseline(DEFAULT_BASELINE))
    assert res.new == [], "new guberlint findings:\n" + "\n".join(
        f.render() for f in res.new
    )
    # a fixed finding whose baseline entry lingers should be pruned, so
    # the ratchet only ever tightens
    assert res.stale_keys == [], (
        "stale baseline entries (run `python -m tools.lint "
        "--update-baseline`): " + ", ".join(res.stale_keys)
    )


def test_baseline_is_not_vacuous():
    # the grandfathered host-sync set must actually be observed — an
    # empty scan (wrong roots, broken walker) must not pass silently.
    # (Floor lowered as the ratchet tightens: the pipelined-dispatch
    # refactor moved the pump's flush-boundary readbacks into the
    # explicitly-pragma'd completion stage, 72 -> 47 GL001 entries.)
    res = run_lint()
    assert len(res.findings) >= 30
    assert {f.rule for f in res.findings} >= {"GL001", "GL003"}


def test_registry_complete():
    codes = {r.code for r in REGISTRY}
    assert codes == {
        "GL000", "GL001", "GL002", "GL003", "GL004", "GL005", "GL006",
        "GL007", "GL008", "GL009", "GL010", "GL011", "GL012", "GL013",
        "GL015", "GL017", "GL018", "GL019",
    }


def test_gl011_field_list_matches_slot_table():
    # GL011 hardcodes the slot-table field names so the linter stays
    # jax-free; this is the lockstep pin (import deferred to keep THIS
    # module's import graph jax-free too — conftest already loaded jax
    # for the suite, but the linter itself must not need it).
    from gubernator_tpu.ops.layout import SlotTable

    from tools.lint.rules import _SLOT_FIELDS

    assert _SLOT_FIELDS == SlotTable._fields


# ---------------------------------------------------------------------------
# per-rule fixture-violation tests

_CASES = [
    (
        "GL001",
        fixture("runtime", "gl001_host_sync.py"),
        {
            "block_until_ready",
            "np.asarray",
            "device_get",
            "int(subscript)",
            "float(subscript)",
        },
        5,
    ),
    (
        "GL002",
        fixture("ops", "gl002_jit_impure.py"),
        {"time.time", "random.random", "os.environ", "time.perf_counter",
         "time.monotonic"},
        5,
    ),
    (
        "GL003",
        fixture("service", "gl003_env_drift.py"),
        {"GUBER_FIXTURE_ONLY_UNDOCUMENTED_KNOB"},
        2,
    ),
    (
        "GL004",
        fixture("service", "gl004_import_env.py"),
        {"os.environ.get", "os.environ['HOME']", "os.getenv",
         "'GUBER_DEBUG' in os.environ"},
        4,
    ),
    (
        "GL005",
        fixture("ops", "gl005_dtype.py"),
        {"jnp.zeros", "jnp.arange", "jnp.asarray", "int32 cast"},
        4,
    ),
    (
        "GL006",
        fixture("parallel", "gl006_swallow.py"),
        {"bare_pass", "bare_except", "tuple_catch"},
        4,  # 3 swallows + 1 reason-less pragma
    ),
    (
        "GL007",
        fixture("runtime", "gl007_span_level.py"),
        {"unlabeled_attr_call", "unlabeled_bare_call",
         "unlabeled_start_span"},
        3,  # leveled kwarg/positional + pragma'd sites don't fire
    ),
    (
        "GL008",
        fixture("service", "gl008_debug_routes.py"),
        {"/debug/engine2", "/debug/raw", "/debug/trigger"},
        3,  # routes inside add_debug_routes (nested included) don't fire
    ),
    (
        "GL009",
        fixture("runtime", "gl009_scrape_device_work.py"),
        {"'live_count'", "'occupancy_stats'", "'debug_snapshot'",
         "jax.numpy.sum", "'add_debug_routes'", "'engine_sync'"},
        6,  # table_census internals, pragma'd gather, helper don't fire
    ),
    (
        "GL010",
        fixture("runtime", "gl010_unaccounted_transfer.py"),
        {"'raw_attr_call'", "'raw_bare_call'", "'raw_in_loop'"},
        3,  # accounted wrapper calls + pragma'd site don't fire
    ),
    (
        "GL011",
        fixture("runtime", "gl011_raw_table_index.py"),
        {"'subscript_attr_chain'", "'subscript_bare_name'",
         "'asarray_pull'"},
        3,  # pragma'd + batch-struct (ib./wb./cols.) sites don't fire
    ),
    (
        "GL012",
        fixture("service", "gl012_provenance.py"),
        {"'serve_unstamped'", "'serve_unstamped_over'"},
        3,  # 2 unstamped answers + 1 reason-less pragma; error=/stamped/
            # recorded/reasoned-pragma sites don't fire
    ),
    (
        "GL013",
        fixture("runtime", "gl013_core_drift.py"),
        {"'ShadowEngine._dispatch'", "'ShadowEngine._complete'"},
        3,  # 2 shadows + 1 reason-less pragma; reasoned-pragma close,
            # dunders, non-core names, module-level defs don't fire
    ),
    (
        "GL015",
        fixture("service", "gl015_slo_parity.py"),
        {"'turbo-freshness'", "requires a non-empty reason"},
        2,  # 1 undocumented spec + 1 reason-less pragma; ids with real
            # "### SLO catalog" rows and the reasoned-pragma spec stay
            # quiet (ghost rows only fire against the real slo.py)
    ),
    (
        "GL017",
        fixture("runtime", "gl017_lock_discipline.py"),
        {"Ledger._rows is guarded by 'engine.bulks'",
         "Ledger._count is guarded by 'engine.bulks'",
         "unlocked_add()", "unlocked_call()", "conditional()",
         "Sub._rows is guarded by 'engine.bulks'", "sub_unlocked()",
         "requires a non-empty reason"},
        6,  # unlocked writes/mutators + 1 reason-less pragma; with-lock,
            # @holds_lock, @init_path, reasoned-pragma, and @thread-
            # affine sites stay quiet (subclass inherits the registry)
    ),
    (
        "GL018",
        fixture("runtime", "gl018_blocking_under_lock.py"),
        {"block_until_ready", "time.sleep", "device_get",
         "requires a non-empty reason"},
        5,  # 4 blocking calls under a hot lock + 1 reason-less pragma;
            # the same calls outside locks or under a cold lock pass
    ),
    (
        "GL019",
        fixture("runtime", "gl019_unbounded_queue.py"),
        {"queue.SimpleQueue", "queue.Queue", "asyncio.Queue",
         "requires a non-empty reason"},
        5,  # 4 unbounded constructions + 1 reason-less pragma; bounded
            # (literal/positional/computed) and reasoned-pragma sites
            # stay quiet
    ),
]


@pytest.mark.parametrize(
    "code,path,needles,expect_n", _CASES, ids=[c[0] for c in _CASES]
)
def test_rule_fires_on_its_fixture(code, path, needles, expect_n):
    res = run_lint(paths=[path], rule_codes=[code])
    mine = [f for f in res.new if f.rule == code]
    assert len(mine) == expect_n, "\n".join(f.render() for f in res.new)
    blob = "\n".join(f.message for f in mine)
    for needle in needles:
        assert needle in blob, f"expected a finding mentioning {needle!r}"


def test_pragma_suppresses_and_requires_reason():
    res = run_lint(
        paths=[fixture("parallel", "gl006_swallow.py")],
        rule_codes=["GL006"],
    )
    msgs = "\n".join(f"{f.line}: {f.message}" for f in res.new)
    # pragma WITH reason (pragma_with_reason, line 42) is suppressed
    assert "pragma_with_reason" not in msgs
    # pragma WITHOUT reason still fails, with an instructive message
    assert "requires a non-empty reason" in msgs
    # clean handlers (logged / narrow catch) are not flagged
    assert "'logged'" not in msgs and "'narrow'" not in msgs


def test_gl001_inline_pragma_suppresses():
    res = run_lint(
        paths=[fixture("runtime", "gl001_host_sync.py")],
        rule_codes=["GL001"],
    )
    # 6 host syncs in the file, one carries allow-host-sync
    lines = {f.line for f in res.new}
    assert len(res.new) == 5 and 16 not in lines


def test_baseline_grandfathers_by_key_count():
    path = fixture("parallel", "gl006_swallow.py")
    clean = run_lint(paths=[path], rule_codes=["GL006"])
    assert len(clean.new) == 4
    # baseline one of the keys: exactly that finding is absorbed
    key = next(f.key for f in clean.new if "bare_pass" in f.message)
    res = run_lint(paths=[path], rule_codes=["GL006"], baseline={key: 1})
    assert len(res.new) == 3
    assert all("bare_pass" not in f.message for f in res.new)
    # a count above the observed one is stale
    res = run_lint(paths=[path], rule_codes=["GL006"], baseline={key: 2})
    assert res.stale_keys == [key]


# ---------------------------------------------------------------------------
# CLI contract


def _cli(*args):
    return subprocess.run(
        [sys.executable, "-m", "tools.lint", *args],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=120,
    )


def test_cli_repo_exits_zero_with_baseline():
    p = _cli("-q")
    assert p.returncode == 0, p.stdout + p.stderr


def test_cli_fixture_exits_nonzero():
    p = _cli(fixture("parallel", "gl006_swallow.py"), "-q")
    assert p.returncode == 1
    assert "GL006" in p.stdout


def test_cli_list_rules():
    p = _cli("--list-rules")
    assert p.returncode == 0
    for code in ("GL000", "GL006", "allow-swallow"):
        assert code in p.stdout


def test_linter_is_stdlib_only():
    """The module rules must run without jax, numpy, or any third-party
    import — `python -S` skips site-packages, so any non-stdlib import
    fails loudly."""
    code = (
        "import sys; sys.path.insert(0, '.');"
        "from tools.lint import run_lint;"
        "r = run_lint(paths=['gubernator_tpu/parallel', 'gubernator_tpu/service']);"
        "assert 'jax' not in sys.modules and 'numpy' not in sys.modules;"
        "print('scanned-ok', len(r.findings))"
    )
    p = subprocess.run(
        [sys.executable, "-S", "-c", code],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert p.returncode == 0, p.stdout + p.stderr
    assert "scanned-ok" in p.stdout


def test_gl015_repo_baseline_zero_and_doc_table_valid():
    # The shipping SLO catalog must be FULLY documented and the doc
    # table must list no ghosts — GL015's repo baseline is pinned at
    # zero in BOTH directions.
    res = run_lint(
        paths=["gubernator_tpu/service/slo.py"], rule_codes=["GL015"]
    )
    assert [f.render() for f in res.new] == []

    from tools.lint.rules import slo_doc_ids

    ids = slo_doc_ids()
    assert ids, 'docs/monitoring.md must carry a "### SLO catalog" table'
    # the doc parse and the live catalog agree exactly
    from gubernator_tpu.service.slo import default_specs

    assert ids == {s.id for s in default_specs()}


def test_gl017_repo_baseline_zero():
    # The lock-discipline protocol ships fully honored: every guarded
    # mutation in the real tree is lexically covered (with-lock body,
    # @holds_lock contract, @init_path) or carries a reasoned pragma —
    # GL017's repo baseline is pinned at zero.
    res = run_lint(rule_codes=["GL017"])
    assert [f.render() for f in res.new] == []
    assert not any(f.rule == "GL017" for f in res.findings)


def test_gl018_repo_baseline_zero():
    # No hot-lock critical section in the real tree performs device
    # syncs, sleeps, futures, or sockets — GL018's repo baseline is
    # pinned at zero.
    res = run_lint(rule_codes=["GL018"])
    assert [f.render() for f in res.new] == []
    assert not any(f.rule == "GL018" for f in res.findings)


def test_gl019_repo_baseline_zero():
    # Every queue on a serving path is bounded (peer batch queue via
    # GUBER_PEER_QUEUE, engine intake via the overload governor) or
    # carries a reasoned pragma naming what bounds its producer —
    # GL019's repo baseline is pinned at zero.
    res = run_lint(rule_codes=["GL019"])
    assert [f.render() for f in res.new] == []
    assert not any(f.rule == "GL019" for f in res.findings)


def test_gl017_parses_real_guarded_declarations():
    # The static rule must see the same protocol the runtime enforces:
    # spot-check that real declarations parse out of their modules with
    # lock attribution (and base-chain merge) intact.
    from tools.lint import iter_py_files, load_modules
    from tools.lint.rules import _module_lock_info

    mods, errs = load_modules(
        iter_py_files(["gubernator_tpu/runtime/pager.py"])
    )
    assert not errs
    pager = _module_lock_info(mods[0])["Pager"]
    assert pager.guarded["page_map"] == "engine.table"
    assert pager.guarded["demotes"] == "w:engine.table"

    mods, errs = load_modules(
        iter_py_files(["gubernator_tpu/runtime/engine.py"])
    )
    assert not errs
    mesh = _module_lock_info(mods[0])["MeshEngine"]
    # base-class chain merge: EngineBase fields + MeshEngine fields
    assert mesh.guarded["_bulks"] == "engine.bulks"
    assert mesh.guarded["table"] == "w:engine.table"
    assert mesh.lock_attrs["_lock"] == "engine.table"


# ---------------------------------------------------------------------------
# dead-pragma pruner + changed-only + perf


def test_repo_has_no_stale_pragmas():
    # Every `guberlint: allow-*` pragma in the tree must still suppress
    # at least one live finding — dead pragmas rot into false comfort.
    res = run_lint()
    assert res.stale_pragmas == [], "\n".join(
        f"{p}:{ln}: dead pragma allow-{name}"
        for p, ln, name in res.stale_pragmas
    )


_SCRATCH_PRAGMAS = (
    "from gubernator_tpu.utils import lockorder, raceguard\n"
    "\n"
    "\n"
    "class Box:\n"
    "    def __init__(self):\n"
    "        self._lock = lockorder.make_lock('engine.bulks')\n"
    "        self._rows = {}\n"
    "\n"
    "    def live(self, k, v):\n"
    "        self._rows[k] = v  "
    "# guberlint: allow-lock-discipline -- scratch: single-thread path\n"
    "\n"
    "    def clean(self):\n"
    "        return 1  "
    "# guberlint: allow-lock-discipline -- nothing mutates here\n"
    "\n"
    "\n"
    "raceguard.guarded_by(Box, {'_rows': 'engine.bulks'})\n"
)


def _scratch_repo(tmp_path, monkeypatch):
    """Point the linter's scan root at a one-file scratch tree: a live
    GL017 pragma (suppresses an unlocked guarded mutation) and a stale
    one (no finding on its line)."""
    import tools.lint as L
    import tools.lint.__main__ as M

    sub = tmp_path / "gubernator_tpu" / "parallel"
    sub.mkdir(parents=True)
    f = sub / "scratch_pragmas.py"
    f.write_text(_SCRATCH_PRAGMAS)
    monkeypatch.setattr(L, "REPO_ROOT", str(tmp_path))
    monkeypatch.setattr(L, "DEFAULT_ROOTS", ("gubernator_tpu",))
    # __main__ imported REPO_ROOT by value; its --fix path joins it.
    monkeypatch.setattr(M, "REPO_ROOT", str(tmp_path))
    monkeypatch.setattr(M, "DEFAULT_ROOTS", ("gubernator_tpu",))
    return f


def test_stale_pragma_detection(tmp_path, monkeypatch):
    # A pragma with no matching finding on its line is stale; a pragma
    # actually suppressing one is not. Scoped to a scratch tree so the
    # repo baseline never interferes.
    _scratch_repo(tmp_path, monkeypatch)
    res = run_lint()
    assert [(ln, name) for _, ln, name in res.stale_pragmas] == [
        (13, "lock-discipline")
    ]


def test_cli_prune_pragmas_reports_and_fixes(tmp_path, monkeypatch, capsys):
    # End-to-end over the real CLI entrypoint: --prune-pragmas lists
    # dead pragmas and exits 1; --fix strips exactly those, keeping
    # live ones and the code on the pruned line.
    from tools.lint.__main__ import main

    f = _scratch_repo(tmp_path, monkeypatch)

    assert main(["--prune-pragmas"]) == 1
    out = capsys.readouterr().out
    assert "scratch_pragmas.py:13: dead pragma allow-lock-discipline" in out

    assert main(["--prune-pragmas", "--fix"]) == 0
    text = f.read_text()
    assert "nothing mutates here" not in text
    assert "single-thread path" in text  # the live pragma survives
    assert "return 1" in text  # code on the pruned line survives

    # a second prune pass finds nothing
    capsys.readouterr()
    assert main(["--prune-pragmas", "-q"]) == 0


def test_prune_pragma_line_unit():
    from tools.lint.__main__ import prune_pragma_line

    # trailing pragma stripped, code kept
    assert (
        prune_pragma_line(
            "    x = 1  # guberlint: allow-swallow -- old", {"swallow"}
        )
        == "    x = 1"
    )
    # pure-comment pragma line prunes to ''
    assert (
        prune_pragma_line("# guberlint: allow-swallow", {"swallow"}) == ""
    )
    # a pragma naming a different rule is left alone
    line = "    x = 1  # guberlint: allow-host-sync -- hot"
    assert prune_pragma_line(line, {"swallow"}) == line
    # mixed pragmas where only one is dead: left for a human
    line = "    x = 1  # guberlint: allow-swallow allow-host-sync -- mixed"
    assert prune_pragma_line(line, {"swallow"}) == line


def test_cli_changed_only_smoke():
    # --changed-only lints the git-diff set under the default roots;
    # the working tree must stay clean (exit 0) — anything it flags
    # would also fail the full-repo gate.
    p = _cli("--changed-only", "-q")
    assert p.returncode == 0, p.stdout + p.stderr


def test_full_repo_lint_is_fast_enough():
    # The shared-AST-walk cache keeps the full 18-rule scan cheap
    # enough for a pre-commit hook. Generous bound: a cold run on a
    # loaded CI box must still clear it.
    import time as _time

    t0 = _time.perf_counter()
    run_lint()
    dt = _time.perf_counter() - t0
    assert dt < 10.0, f"full repo lint took {dt:.1f}s"
