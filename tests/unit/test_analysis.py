"""Static-analysis tests (ISSUES 7 + 9): the pre-dispatch SPMD cell
analyzer (rule-by-rule, plus the never-block-on-unparseable contract),
the IPython source-stripping helper, the preflight finding memory, the
env-knob registry accessors, the framework self-lint passes, and the
ISSUE 9 effect-inference engine (name/collective footprints, opacity,
the session dependency DAG) — including the acceptance gates: the
PR 5 frozen-rank hang cell is an error pre-dispatch AND carries a
non-empty ordered collective footprint, the analyzer has zero
error-severity false positives over the examples/ notebooks and the
selftest corpus, every one of those cells gets a parseable non-opaque
EffectReport, and ``run_self_lint`` is clean over this very checkout
(the CI ``static-analysis`` job as a test) — now covering the gateway
classes and the ``_locked`` helper convention."""

import ast
import json
import os

import pytest

from nbdistributed_tpu.analysis import (cellcheck, ipycompat, preflight,
                                        strip_ipython, vet_cell)
from nbdistributed_tpu.analysis.effects import (collective_class,
                                                infer_effects)
from nbdistributed_tpu.analysis.selfcheck import (_ThreadPass,
                                                  check_env_knobs,
                                                  run_self_lint)
from nbdistributed_tpu.utils import knobs

pytestmark = [pytest.mark.unit, pytest.mark.lint]

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# The exact cell shape tests/integration/test_hang_watchdog.py wedges:
# rank 1's in-branch all_reduce is collective #2 for rank 1 only.
HANG_CELL = """
import jax.numpy as jnp
a = all_reduce(jnp.ones(2))        # collective #1: both ranks join
if rank == 1:
    b = all_reduce(a)              # collective #2: frozen by the plan
'done-%d' % rank
"""


def rules(res, severity=None):
    return [f.rule for f in res.findings
            if severity is None or f.severity == severity]


# ----------------------------------------------------------------------
# rank-conditional collectives


def test_frozen_rank_hang_cell_is_an_error_pre_dispatch():
    res = vet_cell(HANG_CELL)
    assert res.parsed
    errs = res.errors
    assert [f.rule for f in errs] == ["rank-conditional-collective"]
    # The finding points at the in-branch collective, not the safe one.
    assert errs[0].line == 5
    assert "all_reduce" in errs[0].message


def test_process_index_branch_flagged():
    res = vet_cell("if jax.process_index() == 0:\n    barrier()")
    assert rules(res, "error") == ["rank-conditional-collective"]


def test_while_on_rank_flagged():
    res = vet_cell("while rank < 1:\n    x = all_reduce(x)")
    assert rules(res, "error") == ["rank-conditional-collective"]


def test_ternary_on_rank_flagged():
    res = vet_cell("x = all_reduce(y) if rank == 0 else y")
    assert rules(res, "error") == ["rank-conditional-collective"]


def test_rank_conditional_collective_inside_def_body():
    # A def body runs when every rank calls it — the branch inside
    # still diverges, including through the return value expression.
    res = vet_cell("def step():\n"
                   "    if rank == 0:\n"
                   "        return all_reduce(x)")
    assert rules(res, "error") == ["rank-conditional-collective"]


def test_match_on_rank_flagged():
    res = vet_cell("match rank:\n"
                   "    case 0:\n"
                   "        all_reduce(x)\n"
                   "    case _:\n"
                   "        pass\n")
    assert rules(res, "error") == ["rank-conditional-collective"]
    # A rank-dependent case GUARD diverges the same way.
    res = vet_cell("match mode:\n"
                   "    case 'a' if rank == 0:\n"
                   "        barrier()\n")
    assert rules(res, "error") == ["rank-conditional-collective"]
    # Uniform subject, uniform guards: clean.
    assert not vet_cell("match mode:\n"
                        "    case 'a':\n"
                        "        x = all_reduce(x)\n").findings


def test_match_on_rank_exit_desyncs():
    res = vet_cell("match rank:\n"
                   "    case 0:\n"
                   "        raise ValueError('x')\n"
                   "y = all_reduce(x)\n")
    assert rules(res, "error") == ["rank-conditional-exit"]


def test_uniform_condition_is_clean():
    assert not vet_cell(
        "if step % 10 == 0:\n    x = all_reduce(x)").findings


def test_collective_outside_branch_is_clean():
    assert not vet_cell(
        "x = all_reduce(x)\nif rank == 0:\n    print('saved')"
    ).errors


def test_rank_conditional_def_definition_is_not_a_collective():
    # Defining a helper under a rank branch executes no collective.
    res = vet_cell("if rank == 0:\n"
                   "    def helper():\n"
                   "        return all_reduce(x)")
    assert "rank-conditional-collective" not in rules(res, "error")


# ----------------------------------------------------------------------
# rank-conditional exits


def test_raise_before_collectives_desyncs():
    res = vet_cell("if rank == 0:\n"
                   "    raise ValueError('x')\n"
                   "y = all_reduce(x)")
    assert rules(res, "error") == ["rank-conditional-exit"]


def test_raise_after_last_collective_is_clean():
    assert not vet_cell("x = all_reduce(x)\n"
                        "if rank == 0:\n"
                        "    raise ValueError(str(x))").errors


def test_break_skipping_loop_collectives_desyncs():
    res = vet_cell("for i in range(5):\n"
                   "    if rank == 1:\n"
                   "        break\n"
                   "    x = all_reduce(x)")
    assert rules(res, "error") == ["rank-conditional-exit"]


def test_break_in_while_training_loop_desyncs():
    # The most common SPMD loop shape: collectives at the top of a
    # while body, rank-conditional break below — the break skips the
    # remaining ITERATIONS' collectives.
    res = vet_cell("while step < 10:\n"
                   "    g = all_reduce(g)\n"
                   "    if rank == 0:\n"
                   "        break")
    assert rules(res, "error") == ["rank-conditional-exit"]


def test_break_on_uniform_condition_is_clean():
    assert not vet_cell("for i in range(5):\n"
                        "    if done:\n"
                        "        break\n"
                        "    x = all_reduce(x)").errors


# ----------------------------------------------------------------------
# subset rankspec vs collectives


def test_subset_collective_call_is_an_error():
    res = vet_cell("y = all_reduce(x)", ranks=[0], world=4)
    assert rules(res, "error") == ["subset-collective"]


def test_subset_bare_reference_is_a_warning():
    res = vet_cell("alias = all_reduce", ranks=[0], world=4)
    assert rules(res) == ["subset-collective-ref"]
    assert not res.errors


def test_subset_collective_inside_def_is_a_warning():
    res = vet_cell("def f():\n    return all_reduce(x)",
                   ranks=[0, 2], world=4)
    assert "subset-collective" in rules(res, "warning")
    assert not res.errors


def test_full_world_collective_is_clean():
    assert not vet_cell("y = all_reduce(x)",
                        ranks=[0, 1, 2, 3], world=4).findings
    # Duplicate rank listings still cover the world.
    assert not vet_cell("y = all_reduce(x)",
                        ranks=[0, 0, 1], world=2).findings


# ----------------------------------------------------------------------
# host syncs in loops (perf lints stay warnings)


@pytest.mark.parametrize("cell", [
    "for i in range(10):\n    tot += loss.item()",
    "while True:\n    y = jax.device_get(x)",
    "for i in range(3):\n    print(loss)",
    "for i in range(3):\n    vals = x.tolist()",
])
def test_host_sync_in_loop_warns(cell):
    res = vet_cell(cell)
    assert rules(res) == ["host-sync-in-loop"]
    assert not res.errors


def test_host_sync_outside_loop_is_clean():
    assert not vet_cell("tot = loss.item()\nprint(loss)").findings


def test_constant_print_in_loop_is_clean():
    assert not vet_cell("for i in range(3):\n    print('step')"
                        ).findings


# ----------------------------------------------------------------------
# namespace hazards


@pytest.mark.parametrize("cell", [
    "rank = 5",
    "del all_reduce",
    "from mymod import rank",
    "def all_reduce():\n    pass",
    "for rank in range(3):\n    pass",
])
def test_framework_name_shadowing_warns(cell):
    res = vet_cell(cell)
    assert rules(res) == ["namespace-shadow"]
    assert not res.errors


def test_idiomatic_reimports_are_not_hazards():
    assert not vet_cell("import jax\n"
                        "import jax.numpy as jnp\n"
                        "import numpy as np").findings


def test_attribute_and_subscript_writes_are_not_shadowing():
    assert not vet_cell("cfg.rank = 3\nstate['rank'] = 4").findings


# ----------------------------------------------------------------------
# contracts: never block on unparseable, never raise, ordering


def test_unparseable_source_reports_parsed_false_and_no_findings():
    res = vet_cell("def f(:")
    assert not res.parsed and res.findings == []


def test_vet_never_raises_on_weird_input():
    for src in ("", "\x00", "  ", "\n\n", "ловлю = 1",
                "x = " + "(" * 200 + "1" + ")" * 200):
        vet_cell(src, ranks=[0], world=2)


def test_errors_sort_before_warnings_and_dedup():
    res = vet_cell("for i in range(4):\n"
                   "    print(loss)\n"
                   "if rank == 0:\n"
                   "    y = all_reduce(x)\n")
    sevs = [f.severity for f in res.findings]
    assert sevs == sorted(sevs, key=lambda s: 0 if s == "error" else 1)
    keys = [(f.rule, f.line, f.col) for f in res.findings]
    assert len(keys) == len(set(keys))


# ----------------------------------------------------------------------
# ipycompat: line-preserving IPython stripping


def test_strip_line_magic_and_shell_escape_keep_line_numbers():
    src = "%time x = 1\n!pip list\ny = all_reduce(x) if rank==0 else 2"
    cleaned = strip_ipython(src)
    assert cleaned.splitlines()[0] == "pass"
    assert cleaned.splitlines()[1] == "pass"
    res = vet_cell(src)
    assert res.errors and res.errors[0].line == 3


def test_strip_assignment_escape_and_help_suffix():
    cleaned = strip_ipython("files = !ls\nobj.method??\nx = 1")
    lines = cleaned.splitlines()
    assert lines[0] == "pass" and lines[1] == "pass"
    assert lines[2] == "x = 1"
    ast.parse(cleaned)


def test_strip_preserves_indentation():
    cleaned = strip_ipython("for i in range(2):\n    %time f(i)")
    assert cleaned.splitlines()[1] == "    pass"
    ast.parse(cleaned)


def test_modulo_continuation_line_survives():
    src = "y = (x\n% b)"
    assert strip_ipython(src) == src


def test_pure_python_returns_identity():
    src = "a = 1\nb = a % 2\n"
    assert strip_ipython(src) is src


def test_string_literals_are_not_ipython_syntax():
    # A shell-looking line INSIDE a triple-quoted string is data; the
    # cell parses as-is and must come back verbatim — corrupting the
    # string would turn the cell unparseable and blind the vetting.
    src = ('cmd = """\n'
           '!pip install foo\n'
           '"""\n'
           'if rank == 0:\n'
           '    all_reduce(x)\n')
    assert strip_ipython(src) == src
    res = vet_cell(src)
    assert res.parsed
    assert rules(res, "error") == ["rank-conditional-collective"]


def test_mixed_magic_and_multiline_string():
    # A real magic line alongside a multi-line string whose interior
    # line starts with '!': only the magic line is rewritten.
    src = ('%time x = 1\n'
           'tmpl = """\n'
           '!do-not-touch\n'
           '"""\n'
           'if rank == 0:\n'
           '    all_reduce(x)\n')
    cleaned = strip_ipython(src)
    lines = cleaned.splitlines()
    assert lines[0] == "pass"
    assert lines[2] == "!do-not-touch"
    res = vet_cell(src)
    assert res.parsed
    assert rules(res, "error") == ["rank-conditional-collective"]


def test_cell_magic_line_stripped():
    cleaned = strip_ipython("%%time\nx = 1")
    assert cleaned.splitlines()[0] == "pass"
    ast.parse(cleaned)


def test_is_ipython_line_classifier():
    assert ipycompat._is_ipython_line("%time f()")
    assert ipycompat._is_ipython_line("!ls")
    assert ipycompat._is_ipython_line("obj?")
    assert not ipycompat._is_ipython_line("x = y % z")
    assert not ipycompat._is_ipython_line("")


# ----------------------------------------------------------------------
# preflight memory (the "analyzer told you so" loop)


def test_preflight_note_and_lookup_roundtrip():
    preflight.clear()
    res = vet_cell(HANG_CELL)
    preflight.note("sha-abc", res.findings)
    entry = preflight.lookup("sha-abc")
    assert entry is not None
    assert entry["errors"] == 1
    assert "rank-conditional-collective" in entry["rules"]
    assert "rank-conditional-collective" in entry["summary"]
    assert preflight.lookup("sha-unknown") is None
    assert preflight.lookup(None) is None
    preflight.clear()
    assert preflight.lookup("sha-abc") is None


def test_preflight_empty_findings_not_noted():
    preflight.clear()
    preflight.note("sha-clean", [])
    assert preflight.lookup("sha-clean") is None


def test_preflight_is_bounded():
    preflight.clear()
    findings = vet_cell(HANG_CELL).findings
    for i in range(preflight._MAX + 10):
        preflight.note(f"sha-{i}", findings)
    assert preflight.lookup("sha-0") is None          # evicted
    assert preflight.lookup(f"sha-{preflight._MAX + 9}") is not None
    preflight.clear()


def test_summarize_puts_errors_first():
    res = vet_cell("for i in range(3):\n"
                   "    print(loss)\n"
                   "    if rank == 0:\n"
                   "        x = all_reduce(x)")
    s = preflight.summarize(res.findings)
    assert s.startswith("[rank-conditional-collective]")
    assert "more finding" in s


# ----------------------------------------------------------------------
# env-knob registry accessors


def test_undeclared_knob_read_fails_fast():
    with pytest.raises(KeyError, match="NBD_TOTALLY_BOGUS"):
        knobs.get_raw("NBD_TOTALLY_BOGUS")


def test_knob_accessor_semantics():
    env = {"NBD_HANG": "off", "NBD_HANG_SKEW_S": "2.5",
           "NBD_FLIGHT_RING_BYTES": "1024",
           "NBD_ORPHAN_TTL_S": "soon"}
    assert knobs.get_bool("NBD_HANG", True, env=env) is False
    assert knobs.get_bool("NBD_FLIGHT", True, env=env) is True
    assert knobs.get_float("NBD_HANG_SKEW_S", 20.0, env=env) == 2.5
    assert knobs.get_int("NBD_FLIGHT_RING_BYTES", 0, env=env) == 1024
    # Typo'd numeric knobs degrade to the default, never crash.
    assert knobs.get_float("NBD_ORPHAN_TTL_S", 600.0, env=env) == 600.0
    assert knobs.get_str("NBD_RUN_DIR", "-", env=env) == "-"


def test_knob_table_documents_every_knob():
    table = knobs.knob_table_markdown()
    for name in knobs.KNOBS:
        assert f"`{name}`" in table


# ----------------------------------------------------------------------
# framework self-lint (the CI static-analysis gate, as a test)


def test_self_lint_clean_on_this_checkout():
    results = run_self_lint(REPO)
    # All TEN passes, none skippable: the four registry/discipline
    # passes, the three concur lock passes, and the three ISSUE 15
    # lifecycle passes.
    assert set(results) == {"env-knobs", "codec-headers",
                            "thread-shared-state",
                            "protocol-coverage", "lock-order",
                            "blocking-under-lock",
                            "callback-under-lock",
                            "resource-leak", "bracket-discipline",
                            "shutdown-completeness"}
    for name, findings in results.items():
        assert findings == [], (
            f"[{name}] " + "; ".join(f.render() for f in findings))


def test_cli_repo_root_resolution(tmp_path, monkeypatch):
    from nbdistributed_tpu.analysis.cli import _repo_root, main
    assert _repo_root("/explicit/x") == "/explicit/x"
    # This checkout: README.md sits next to the package dir.
    assert _repo_root(None) == REPO
    # No checkout anywhere (package parent is faked away, cwd bare):
    # --self must refuse with a clear exit code, not flag every knob
    # as undocumented against a missing README.
    monkeypatch.chdir(tmp_path)
    import nbdistributed_tpu
    monkeypatch.setattr(nbdistributed_tpu, "__file__",
                        str(tmp_path / "site-packages"
                            / "nbdistributed_tpu" / "__init__.py"))
    assert _repo_root(None) is None
    assert main(["--self"]) == 2


def test_env_knob_pass_catches_undeclared_knob(tmp_path):
    pkg = tmp_path / "nbdistributed_tpu"
    pkg.mkdir()
    (tmp_path / "tools").mkdir()
    (pkg / "mod.py").write_text(
        "import os\nX = os.environ.get('NBD_BOGUS_KNOB')\n")
    findings = check_env_knobs(str(tmp_path))
    assert any(f.rule == "env-knob" and "NBD_BOGUS_KNOB" in f.message
               for f in findings)


@pytest.mark.parametrize("module", ["attention", "decode", "grouped",
                                    "xent"])
def test_no_kernel_module_loads_a_table(module):
    """A kernel chooses its tiles from the call's shapes or from a
    constant in its source: no kernel module imports ``json``, opens a
    file, or keeps a name with ``TUNED`` in it for a file to fill."""
    path = os.path.join(REPO, "nbdistributed_tpu", "ops", module + ".py")
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), path)
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            names.add(getattr(node, "module", None) or "")
            for a in node.names:
                names |= {a.name, a.asname or ""}
        elif isinstance(node, ast.Name):
            names.add(node.id)
        elif isinstance(node, ast.Attribute):
            names.add(node.attr)
    names = {part for n in names for part in n.split(".")}
    assert not names & {"json", "open"}
    assert not [n for n in names if "TUNED" in n.upper()]


def _thread_findings(src, exempt=None):
    tree = ast.parse(src)
    cls = tree.body[0]
    fn = [n for n in cls.body if isinstance(n, ast.FunctionDef)
          and n.name != "__init__"][0]
    p = _ThreadPass("x.py", cls.name, {"counts"}, exempt or {})
    p.visit(fn)
    return p.findings


_THREAD_SRC = """
class C:
    def __init__(self):
        self._lock = None
        self.counts = dict()
    def bump(self):
        <BODY>
"""


def test_thread_pass_flags_unlocked_mutation():
    src = _THREAD_SRC.replace("<BODY>", "self.counts['a'] = 1")
    assert _thread_findings(src)
    src = _THREAD_SRC.replace("<BODY>", "self.n += 1")
    assert _thread_findings(src)


def test_thread_pass_accepts_locked_mutation_and_exemptions():
    src = _THREAD_SRC.replace(
        "<BODY>", "with self._lock:\n            self.counts['a'] = 1")
    assert not _thread_findings(src)
    src = _THREAD_SRC.replace("<BODY>", "self.n += 1")
    assert not _thread_findings(src, exempt={"C.n": "single writer"})


# ----------------------------------------------------------------------
# acceptance corpus: zero error-severity false positives


def _notebook_cells(path):
    with open(path, encoding="utf-8") as f:
        nb = json.load(f)
    for cell in nb.get("cells", []):
        if cell.get("cell_type") == "code":
            yield "".join(cell.get("source", []))


def _subset_context(src, world):
    """Mirror the magic layer: a leading ``%%rank [spec]`` arms the
    subset rule with the parsed ranks."""
    from nbdistributed_tpu.magics import rankspec
    first = src.splitlines()[0].strip() if src.strip() else ""
    if first.startswith("%%rank"):
        spec = first[len("%%rank"):].strip()
        try:
            return rankspec.parse_ranks(spec, world), world
        except rankspec.RankSpecError:
            return None, None
    return None, world


@pytest.mark.parametrize("nb", ["00_quickstart.ipynb",
                                "01_parallelism.ipynb",
                                "02_finetune.ipynb"])
def test_no_error_false_positives_in_example_notebooks(nb):
    path = os.path.join(REPO, "examples", nb)
    bad = []
    for i, src in enumerate(_notebook_cells(path)):
        ranks, world = _subset_context(src, world=2)
        res = vet_cell(src, ranks=ranks, world=world)
        for f in res.errors:
            bad.append(f"{nb} cell {i} L{f.line}: [{f.rule}] "
                       f"{f.snippet.strip()}")
    assert not bad, "\n".join(bad)


def _selftest_cells():
    """Every cell the selftest dispatches: the inline one-liners plus
    the big ``*_cell`` string assignments, extracted from the module
    source so the corpus cannot drift from the code."""
    path = os.path.join(REPO, "nbdistributed_tpu", "selftest.py")
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read())
    cells = []
    for node in ast.walk(tree):
        if (isinstance(node, ast.Assign)
                and any(isinstance(t, ast.Name)
                        and t.id.endswith("_cell")
                        for t in node.targets)
                and isinstance(node.value, ast.Constant)
                and isinstance(node.value.value, str)):
            cells.append(node.value.value)
        # Inline cells: string literals passed to send_to_all /
        # send_to_ranks "execute" calls.
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in ("send_to_all", "send_to_ranks")):
            for arg in node.args:
                if isinstance(arg, ast.Constant) \
                        and isinstance(arg.value, str) \
                        and arg.value not in ("execute", "sync",
                                              "get_status",
                                              "checkpoint", "trace",
                                              "metrics"):
                    cells.append(arg.value)
    assert len(cells) >= 8
    return cells


def test_no_error_false_positives_in_selftest_corpus():
    bad = []
    for i, src in enumerate(_selftest_cells()):
        res = vet_cell(src, ranks=None, world=2)
        for f in res.errors:
            bad.append(f"selftest cell {i} L{f.line}: [{f.rule}] "
                       f"{f.snippet.strip()}")
    assert not bad, "\n".join(bad)


def test_integration_hang_cells_classified_correctly():
    # The deliberately-hazardous watchdog cell IS an error…
    assert vet_cell(HANG_CELL).errors
    # …while its companions (uniformly slow, rank-local infinite
    # loop, post-hang realignment) carry no error findings.
    clean = [
        "import time\ntime.sleep(0.5)\n'slow-%d' % rank",
        "if rank == 1:\n    while True:\n        pass\n'ok-%d' % rank",
        "float(all_reduce(jnp.ones(2))[0])",
    ]
    for src in clean:
        assert not vet_cell(src).errors, src


# ----------------------------------------------------------------------
# magic-layer wiring: _vet_cell gates dispatch


@pytest.fixture
def magic(monkeypatch, tmp_path):
    """A DistributedMagics instance with a fake 2-rank world and no
    IPython shell — enough surface for the pre-dispatch vet path."""
    from nbdistributed_tpu.magics.magic import DistributedMagics
    monkeypatch.setenv("NBD_FLIGHT", "0")
    monkeypatch.setenv("NBD_RUN_DIR", str(tmp_path))
    monkeypatch.setattr(DistributedMagics, "_world", 2)
    monkeypatch.setattr(DistributedMagics, "_lint_mode", "warn")
    preflight.clear()
    yield DistributedMagics.__new__(DistributedMagics)
    preflight.clear()


def test_magic_warn_mode_annotates_and_dispatches(magic, capsys):
    from nbdistributed_tpu.runtime.collective_guard import cell_hash
    assert magic._vet_cell(HANG_CELL, [0, 1]) is True
    out = capsys.readouterr().out
    assert "rank-conditional-collective" in out
    # Dispatched-despite-findings cells are remembered by hash so a
    # later hang verdict cites the pre-flight finding.
    note = preflight.lookup(cell_hash(HANG_CELL))
    assert note is not None and note["errors"] == 1


def test_magic_strict_mode_blocks_error_cells(magic, capsys):
    from nbdistributed_tpu.magics.magic import DistributedMagics
    DistributedMagics._lint_mode = "strict"
    assert magic._vet_cell(HANG_CELL, [0, 1]) is False
    assert "NOT dispatched" in capsys.readouterr().out
    # Warnings alone never block, even under strict.
    assert magic._vet_cell(
        "for i in range(3):\n    print(loss)", [0, 1]) is True


def test_magic_per_cell_strict_flag_blocks(magic):
    assert magic._vet_cell(HANG_CELL, [0, 1], strict=True) is False


def test_magic_off_mode_skips_analysis(magic, capsys):
    from nbdistributed_tpu.magics.magic import DistributedMagics
    DistributedMagics._lint_mode = "off"
    assert magic._vet_cell(HANG_CELL, [0, 1]) is True
    assert capsys.readouterr().out == ""


def test_magic_per_cell_strict_overrides_off_mode(magic, capsys):
    # An explicit `%%distributed --strict` must vet (and block) even
    # when the session mode is off — the flag is a per-cell request.
    from nbdistributed_tpu.magics.magic import DistributedMagics
    DistributedMagics._lint_mode = "off"
    assert magic._vet_cell(HANG_CELL, [0, 1], strict=True) is False
    assert "NOT dispatched" in capsys.readouterr().out


def test_magic_unparseable_never_blocks_even_strict(magic, capsys):
    from nbdistributed_tpu.magics.magic import DistributedMagics
    DistributedMagics._lint_mode = "strict"
    assert magic._vet_cell("def f(:", [0, 1]) is True
    # Unparseable subset cells degrade to the legacy regex warning.
    assert magic._vet_cell("def f(:\nall_reduce(x)", [0]) is True
    assert "deadlock" in capsys.readouterr().out.lower()


def test_magic_findings_counted_in_metrics(magic):
    from nbdistributed_tpu.observability import metrics as obs_metrics
    c = obs_metrics.registry().counter(
        "nbd_lint_findings_total",
        "pre-dispatch cell-vetting findings",
        {"rule": "rank-conditional-collective"})
    before = c.value
    magic._vet_cell(HANG_CELL, [0, 1])
    assert c.value == before + 1


def test_magic_lint_mode_resolution(magic, monkeypatch):
    from nbdistributed_tpu.magics.magic import DistributedMagics
    DistributedMagics._lint_mode = None
    monkeypatch.setenv("NBD_LINT", "strict")
    assert DistributedMagics._lint_mode_now() == "strict"
    monkeypatch.setenv("NBD_LINT", "bogus")
    assert DistributedMagics._lint_mode_now() == "warn"
    DistributedMagics._lint_mode = "off"       # %dist_lint pin wins
    assert DistributedMagics._lint_mode_now() == "off"


# ----------------------------------------------------------------------
# ISSUE 9: effect inference — name footprint


def test_name_footprint_binds_mutations_deletes():
    r = infer_effects("x = a + b\n"
                      "c.cfg = 2\n"
                      "d[k] = 3\n"
                      "lst.append(9)\n"
                      "e += 1\n"
                      "del f\n")
    assert r.parsed and not r.opaque
    assert {"a", "b", "c", "d", "e", "k", "lst"} <= r.reads
    assert r.writes == {"x", "e"}
    assert r.mutates == {"c", "d", "lst"}
    assert r.deletes == {"f"}
    # touched = the DAG's write side.
    assert r.touched == {"x", "e", "c", "d", "lst", "f"}


def test_footprint_free_reads_exclude_cell_local_bindings():
    r = infer_effects("x = 1\ny = x + z")
    assert "x" not in r.reads          # bound before the read
    assert "z" in r.reads
    # …but a deleted name read later is free again.
    r = infer_effects("x = 1\ndel x\ny = x")
    assert "x" in r.reads


def test_footprint_global_escape_and_augassign():
    r = infer_effects("def bump():\n"
                      "    global counter\n"
                      "    counter = counter + 1\n"
                      "bump()")
    assert "counter" in r.writes       # escapes the def
    assert "counter" in r.reads
    r = infer_effects("tot += loss")
    assert "tot" in r.writes and "tot" in r.reads


def test_footprint_imports_and_walrus_and_for_target():
    r = infer_effects("import numpy as np\n"
                      "from math import sqrt\n"
                      "for i in range(3):\n"
                      "    pass\n"
                      "n = (m := 7)\n")
    assert {"np", "sqrt", "i", "n", "m"} <= r.writes


def test_comprehension_scope_not_module_writes():
    r = infer_effects("ys = [w * xi for xi in xs]")
    assert "xi" not in r.writes
    assert {"w", "xs"} <= r.reads and "ys" in r.writes
    assert r.collective_verdict == "none"


@pytest.mark.parametrize("cell,why", [
    ("exec('x=1')", "exec"),
    ("y = eval(s)", "eval"),
    ("from jax.numpy import *", "star-import"),
    ("globals()['q'] = 7", "globals"),
    ("vars().update(d)", "vars"),
])
def test_dynamic_escapes_are_opaque(cell, why):
    r = infer_effects(cell)
    assert r.opaque, cell
    assert any(why in reason for reason in r.opaque_reasons)
    assert collective_class(r) == "unknown"


def test_unparseable_source_is_opaque_not_raised():
    r = infer_effects("def f(:")
    assert not r.parsed and r.opaque
    assert collective_class(r) == "unknown"


def test_reading_globals_is_not_opaque():
    r = infer_effects("names = sorted(globals())")
    assert not r.opaque


# ----------------------------------------------------------------------
# ISSUE 9: effect inference — collective footprint


def test_collective_footprint_ordered_sites():
    r = infer_effects(HANG_CELL)
    assert r.parsed and not r.opaque
    assert [s.op for s in r.collectives] == ["all_reduce",
                                             "all_reduce"]
    lines = [s.line for s in r.collectives]
    assert lines == sorted(lines) and len(set(lines)) == 2
    assert r.collectives[1].conditional
    assert r.collective_verdict == "exact"
    assert collective_class(r) == "bearing"


def test_proven_free_cell():
    r = infer_effects("import time\n"
                      "time.sleep(0.5)\n"
                      "zz = sorted([3, 1])\n"
                      "zz")
    assert r.collective_verdict == "none"
    assert collective_class(r) == "free"
    assert r.collective_free


def test_safe_roots_and_builtins_stay_free():
    r = infer_effects("import numpy as np\n"
                      "a = np.ones(3)\n"
                      "b = jnp.ones(3).sum()\n"
                      "c = math.sqrt(float(len(str(2))))\n"
                      "hist = []\nhist.append(c)")
    assert r.collective_verdict == "none", r.taints


def test_unvetted_calls_taint_to_unknown():
    r = infer_effects("y = train_step(x)")
    assert r.collective_verdict == "unknown"
    assert any("train_step" in t for t in r.taints)
    assert collective_class(r) == "unknown"
    # jax.* is NOT a safe root: jitted products can hide collectives.
    r = infer_effects("f = jax.jit(g)")
    assert r.collective_verdict == "unknown"


def test_same_cell_def_resolved_one_level():
    r = infer_effects("def step(x):\n"
                      "    return all_reduce(x) + 1\n"
                      "y = step(y0)")
    assert [s.op for s in r.collectives] == ["all_reduce"]
    assert r.collectives[0].via == "step"
    assert r.collective_verdict == "exact"


def test_nested_def_call_taints_and_recursion_terminates():
    r = infer_effects("def inner(x):\n"
                      "    return other(x)\n"
                      "def outer(x):\n"
                      "    return inner(x)\n"
                      "outer(1)")
    assert r.collective_verdict == "unknown"
    assert any("one level deep" in t for t in r.taints)
    # A recursive def must terminate with an honest unknown, not
    # recurse forever.
    r = infer_effects("def f(n):\n    return f(n - 1)\nf(3)")
    assert r.collective_verdict == "unknown"


def test_uncalled_def_with_collective_is_free():
    # Defining a helper runs nothing; only a CALL reaches the mesh.
    r = infer_effects("def helper(x):\n    return all_reduce(x)")
    assert r.collectives == ()
    assert r.collective_verdict == "none"


def test_def_escaping_as_argument_is_classified():
    """A def passed INTO a call escapes: the callee may invoke it, so
    its collectives run with no visible site — `list(map(step, data))`
    must not be falsely proven free."""
    r = infer_effects("def step(x):\n"
                      "    return psum(x)\n"
                      "list(map(step, data))")
    assert r.collective_verdict == "unknown"
    assert any("step" in t and "passed to a call" in t
               for t in r.taints)
    # Precision kept: a PROVABLY free body may escape anywhere.
    r = infer_effects("def key(x):\n"
                      "    return x + 1\n"
                      "zz = sorted(data, key=key)")
    assert r.collective_verdict == "none", r.taints
    # A def escaping before/outside its (conditional) statement has no
    # resolvable body — taint, never guess.
    r = infer_effects("if flag:\n"
                      "    def f(x):\n"
                      "        return all_reduce(x)\n"
                      "list(map(f, xs))")
    assert r.collective_verdict == "unknown"
    # Recursive escape terminates with an honest unknown.
    r = infer_effects("def f(x):\n"
                      "    return list(map(f, x))\n"
                      "f(q)")
    assert r.collective_verdict == "unknown"


def test_def_alias_and_shadowed_builtin_escapes():
    """`g = step` must carry step's classification (aliases escape
    the same way defs do), and a rebound builtin must stay rebound
    inside escape-checked bodies."""
    r = infer_effects("def step(x):\n"
                      "    return psum(x)\n"
                      "g = step\n"
                      "zz = sorted(xs, key=g)")
    assert r.collective_verdict == "unknown", r.taints
    assert infer_effects("def step(x):\n"
                         "    return -x\n"
                         "g = step\n"
                         "zz = sorted(xs, key=g)"
                         ).collective_verdict == "none"
    # Alias chains, and aliases CALLED directly, resolve the body.
    r = infer_effects("def step(x):\n"
                      "    return psum(x)\n"
                      "g = step\nh = g\nlist(map(h, xs))")
    assert r.collective_verdict == "unknown"
    r = infer_effects("def step(x):\n"
                      "    return psum(x)\n"
                      "g = step\ng(x0)")
    assert r.collective_verdict == "exact"
    # `float = bad_fn` earlier in the cell: the escaped body's
    # `float(x)` call is no longer a provably inert builtin.
    r = infer_effects("float = bad_fn\n"
                      "def step(x):\n"
                      "    return float(x)\n"
                      "list(map(step, xs))")
    assert r.collective_verdict == "unknown"


def test_class_decorator_application_is_classified():
    r = infer_effects("@my_decorator\nclass C:\n    pass")
    assert r.collective_verdict == "unknown"
    assert any("class decorator" in t for t in r.taints)
    # Safe-module class decorators introspect only — still provable,
    # in both bare and factory form.
    assert infer_effects("from dataclasses import dataclass\n"
                         "@dataclass\nclass C:\n    x: int = 0"
                         ).collective_verdict == "none"
    assert infer_effects("from dataclasses import dataclass\n"
                         "@dataclass(frozen=True)\n"
                         "class C:\n    x: int = 0"
                         ).collective_verdict == "none"


def test_lambda_escape_and_lambda_assignment():
    r = infer_effects("zz = sorted(xs, key=lambda a: all_reduce(a))")
    assert r.collective_verdict == "unknown"
    assert any("lambda" in t for t in r.taints)
    assert infer_effects("zz = sorted(xs, key=lambda a: a[0])"
                         ).collective_verdict == "none"
    # A lambda-assigned name is a same-cell function definition: it
    # resolves at calls and escape-checks as an argument.
    r = infer_effects("g = lambda x: all_reduce(x)\nlist(map(g, xs))")
    assert r.collective_verdict == "unknown"
    assert infer_effects("g = lambda x: x + 1\nlist(map(g, xs))"
                         ).collective_verdict == "none"
    r = infer_effects("g = lambda x: all_reduce(x)\ny = g(x0)")
    assert [s.op for s in r.collectives] == ["all_reduce"]
    # Annotated-assign and walrus lambda bindings are the same hole.
    assert infer_effects("g: object = lambda x: all_reduce(x)\n"
                         "list(map(g, xs))"
                         ).collective_verdict == "unknown"
    assert infer_effects("y = (g := (lambda x: all_reduce(x)))\n"
                         "list(map(g, xs))"
                         ).collective_verdict == "unknown"
    assert infer_effects("g: object = lambda x: -x\nlist(map(g, xs))"
                         ).collective_verdict == "none"


def test_decorator_application_is_classified():
    """`@dec` calls `dec(f)` at definition time — a call site, not an
    expression read (the `@my_decorator` false-free)."""
    r = infer_effects("@my_decorator\ndef g():\n    pass")
    assert r.collective_verdict == "unknown"
    assert any("my_decorator" in t for t in r.taints)
    # Safe-module decorator over a provably free body stays proven.
    r = infer_effects("import functools\n"
                      "@functools.cache\n"
                      "def f():\n    return 1\n"
                      "v = f()")
    assert r.collective_verdict == "none", r.taints
    # …but not over a collective-bearing body (the product calls it).
    r = infer_effects("import functools\n"
                      "@functools.cache\n"
                      "def f():\n    return all_reduce(x)")
    assert r.collective_verdict == "unknown"
    # Factory form: the product that wraps f is a dynamic callee.
    r = infer_effects("@retry(3)\ndef f():\n    pass")
    assert r.collective_verdict == "unknown"
    # A same-cell decorator may return ANYTHING: later calls to the
    # decorated name must not resolve the raw body.
    r = infer_effects("def deco(fn):\n"
                      "    return other_fn\n"
                      "@deco\ndef f():\n    pass\n"
                      "f()")
    assert r.collective_verdict == "unknown"
    # Descriptor builtins never invoke at application time: defining
    # a class with decorated methods stays proven free.
    r = infer_effects("class C:\n"
                      "    @staticmethod\n"
                      "    def m(x):\n"
                      "        return x + 1\n"
                      "    @property\n"
                      "    def v(self):\n"
                      "        return self._v")
    assert r.collective_verdict == "none", r.taints


def test_call_before_def_resolves_earlier_binding():
    """Resolution honors source order: `f = g; f(); def f(): pass`
    invokes g at runtime — the later (collective-free) body proves
    nothing about the call."""
    r = infer_effects("f = unvetted_fn\nf()\ndef f():\n    pass")
    assert r.collective_verdict == "unknown"
    assert any("f()" in t for t in r.taints)
    # The earlier binding CAN be provably safe on its own terms.
    r = infer_effects("from math import sqrt\n"
                      "v = sqrt(2)\n"
                      "def sqrt(x):\n    return all_reduce(x)")
    assert r.collective_verdict == "none", r.taints
    # After the def statement, the body resolves as before.
    r = infer_effects("def f():\n    pass\nf()")
    assert r.collective_verdict == "none"


def test_rebound_safe_root_and_rebound_def_lose_their_proofs():
    r = infer_effects("time = Trainer()\ntime.step()")
    assert r.collective_verdict == "unknown"
    r = infer_effects("def f():\n    pass\nf = trainer.step\nf()")
    assert r.collective_verdict == "unknown"


def test_cross_cell_safe_root_rebind_poisons_later_proofs():
    """A rebind in cell 1 must not let cell 2 be falsely PROVEN free:
    ambient_poison feeds the next cell's assume_unsafe."""
    from nbdistributed_tpu.analysis.effects import ambient_poison
    cell1 = infer_effects("np = weird_module")
    poison = ambient_poison(cell1)
    assert "np" in poison
    # Without the poison, cell 2 would be proven free — the hole.
    assert infer_effects("y = np.sum(x)").collective_verdict == "none"
    r = infer_effects("y = np.sum(x)", assume_unsafe=poison)
    assert r.collective_verdict == "unknown"
    # Builtins poison the same way (`float = my_fn` in cell 1).
    poison2 = ambient_poison(infer_effects("float = my_fn"))
    assert "float" in poison2
    assert infer_effects("z = float(x)",
                         assume_unsafe=poison2
                         ).collective_verdict == "unknown"


def test_reimport_rearms_instead_of_poisoning():
    from nbdistributed_tpu.analysis.effects import ambient_poison
    # `import numpy as np` RESTORES the assumption — no poison…
    assert "np" not in ambient_poison(
        infer_effects("import numpy as np\na = np.ones(2)"))
    # …and a poisoned root is re-armed within the importing cell.
    r = infer_effects("import numpy as np\na = np.ones(2)",
                      assume_unsafe=frozenset({"np"}))
    assert r.collective_verdict == "none"
    # But `import jax as np` both disarms in-cell and poisons onward.
    p = ambient_poison(infer_effects("import jax as np"))
    assert "np" in p


def test_opaque_cell_poisons_every_ambient_assumption():
    from nbdistributed_tpu.analysis.effects import (SAFE_CALL_ROOTS,
                                                    ambient_poison)
    p = ambient_poison(infer_effects("exec(payload)"))
    assert SAFE_CALL_ROOTS <= p and "float" in p


def test_host_sync_flags_and_taint():
    r = infer_effects("for i in range(5):\n    tot += loss.item()")
    assert r.host_sync and r.host_sync_in_loop
    assert r.collective_verdict == "unknown"   # may gather cross-host
    r = infer_effects("v = loss.item()")
    assert r.host_sync and not r.host_sync_in_loop
    r = infer_effects("for i in range(3):\n    print(loss)")
    assert r.host_sync_in_loop
    r = infer_effects("print('hello')")
    assert not r.host_sync


def test_pure_property():
    assert infer_effects("1 + 1").pure
    assert not infer_effects("x = 1").pure
    assert not infer_effects("y = all_reduce(x)").pure


def test_effects_report_as_dict_is_json_safe():
    d = infer_effects(HANG_CELL).as_dict()
    json.dumps(d)
    assert d["collective_verdict"] == "exact"
    assert [s["op"] for s in d["collectives"]] == ["all_reduce",
                                                   "all_reduce"]


def test_await_collective_counts():
    r = infer_effects("r = await all_reduce(jnp.ones(2))")
    assert r.parsed
    assert [s.op for s in r.collectives] == ["all_reduce"]
    assert collective_class(r) == "bearing"


# ----------------------------------------------------------------------
# ISSUE 9: preflight effect store + session dependency DAG


def test_note_effects_log_and_lookup():
    preflight.clear()
    preflight.note_effects("sha-a", infer_effects("x = 1"))
    preflight.note_effects("sha-b", infer_effects("y = x"))
    log = preflight.effects_log()
    assert [e["sha"] for e in log] == ["sha-a", "sha-b"]
    assert preflight.effects_for("sha-b")["reads"] == ["x"]
    assert preflight.effects_for("missing") is None
    preflight.clear()
    assert preflight.effects_log() == []


def test_deps_dag_write_read_edges():
    preflight.clear()
    for sha, src in [("s0", "x = 1\ny = 2"),
                     ("s1", "z = x + 1"),
                     ("s2", "import time\ntime.sleep(0)"),
                     ("s3", "cfg.lr = x"),   # mutation counts as write
                     ("s4", "v = cfg")]:
        preflight.note_effects(sha, infer_effects(src))
    dag = preflight.deps_dag()
    edges = {(e["src"], e["dst"]): e["names"] for e in dag["edges"]}
    assert edges[(0, 1)] == ["x"]
    assert edges[(3, 4)] == ["cfg"]
    assert (0, 2) not in edges and (1, 2) not in edges
    preflight.clear()


def test_deps_dag_war_and_waw_hazards():
    """No-edge must mean REORDERABLE: anti (read→write) and output
    (write→write) hazards get edges too, not just write→read."""
    preflight.clear()
    preflight.note_effects("i", infer_effects("y = x + 1"))
    preflight.note_effects("j", infer_effects("x = 5"))
    dag = preflight.deps_dag()
    edges = {(e["src"], e["dst"]): e["names"] for e in dag["edges"]}
    assert edges[(0, 1)] == ["x"]       # WAR: i reads x, j writes it
    preflight.clear()
    preflight.note_effects("i", infer_effects("x = 1"))
    preflight.note_effects("j", infer_effects("x = 2"))
    dag = preflight.deps_dag()
    edges = {(e["src"], e["dst"]): e["names"] for e in dag["edges"]}
    assert edges[(0, 1)] == ["x"]       # WAW: final value is ordered
    preflight.clear()


def test_deps_dag_opaque_poisons_both_directions():
    preflight.clear()
    for sha, src in [("s0", "a = 1"),
                     ("s1", "exec('b = 2')"),
                     ("s2", "c = 3")]:
        preflight.note_effects(sha, infer_effects(src))
    dag = preflight.deps_dag()
    edges = {(e["src"], e["dst"]): e["names"] for e in dag["edges"]}
    assert edges[(0, 1)] == ["*"]
    assert edges[(1, 2)] == ["*"]
    assert (0, 2) not in edges
    preflight.clear()


def test_effects_log_is_bounded():
    preflight.clear()
    rep = infer_effects("x = 1")
    for i in range(preflight._MAX_CELLS + 10):
        preflight.note_effects(f"s{i}", rep)
    log = preflight.effects_log()
    assert len(log) == preflight._MAX_CELLS
    assert log[0]["sha"] == "s10"      # oldest evicted
    preflight.clear()


# ----------------------------------------------------------------------
# ISSUE 9 satellite: cell magics other than %%distributed/%%rank


def test_nested_python_body_cell_magic_still_vets_remainder():
    for head in ("%%time", "%%time -n1", "%%capture out", "%%prun"):
        src = f"{head}\nif rank == 0:\n    all_reduce(x)\n"
        res = vet_cell(src)
        assert res.parsed, head
        assert rules(res, "error") == ["rank-conditional-collective"], \
            head


def test_non_python_cell_magic_masks_whole_cell():
    for src in ("%%bash\necho hi there\n",
                "%%writefile out.py\nthis is : not python\n",
                "%%sql\nselect * from t where x > 2\n"):
        res = vet_cell(src)
        assert res.parsed and res.findings == [], src
        rep = infer_effects(src)
        assert rep.parsed and not rep.opaque
        assert rep.collective_verdict == "none"
        # Masked payloads still have REAL host side effects (files,
        # subprocesses): never pure/reorderable, though mesh-silent.
        assert rep.host_sync and not rep.pure, src
    # Line count survives the masking (finding lines stay honest).
    assert len(strip_ipython("%%bash\necho hi\necho bye\n")
               .splitlines()) == 3


def test_bare_double_percent_line_is_stripped():
    res = vet_cell("%%\nif rank == 0:\n    all_reduce(x)\n")
    assert res.parsed
    assert rules(res, "error") == ["rank-conditional-collective"]


# ----------------------------------------------------------------------
# ISSUE 9 satellite: async cells — pin the rule semantics


def test_top_level_await_cell_is_vetted():
    # ast.parse accepts module-level await (the error is compile-
    # stage), so IPython's top-level-await cells are NOT unparseable.
    res = vet_cell("import asyncio\n"
                   "await asyncio.sleep(0)\n"
                   "if rank == 0:\n"
                   "    await all_reduce(x)\n")
    assert res.parsed
    assert rules(res, "error") == ["rank-conditional-collective"]


def test_async_for_break_desyncs_like_plain_for():
    res = vet_cell("async def main():\n"
                   "    async for b in stream:\n"
                   "        if rank == 1:\n"
                   "            break\n"
                   "        x = all_reduce(b)\n"
                   "await main()\n")
    assert "rank-conditional-exit" in rules(res, "error")


def test_async_for_host_sync_warns_like_plain_for():
    res = vet_cell("async def main():\n"
                   "    async for b in stream:\n"
                   "        print(loss)\n"
                   "await main()\n")
    assert rules(res) == ["host-sync-in-loop"]


def test_rank_exit_in_async_def_with_collectives_ahead():
    res = vet_cell("async def step():\n"
                   "    if rank == 0:\n"
                   "        return\n"
                   "    y = all_reduce(x)\n")
    assert "rank-conditional-exit" in rules(res, "error")


def test_uniform_async_cell_is_clean():
    assert not vet_cell("async def main():\n"
                        "    y = all_reduce(x)\n"
                        "    return y\n"
                        "await main()\n").errors


# ----------------------------------------------------------------------
# ISSUE 9: effect-engine acceptance corpora (the CI effects check)


@pytest.mark.parametrize("nb", ["00_quickstart.ipynb",
                                "01_parallelism.ipynb",
                                "02_finetune.ipynb"])
def test_example_notebook_cells_get_non_opaque_reports(nb):
    path = os.path.join(REPO, "examples", nb)
    bad = []
    for i, src in enumerate(_notebook_cells(path)):
        rep = infer_effects(src)
        if not rep.parsed or rep.opaque:
            bad.append(f"{nb} cell {i}: {rep.opaque_reasons}")
    assert not bad, "\n".join(bad)


def test_selftest_corpus_cells_get_non_opaque_reports():
    bad = []
    for i, src in enumerate(_selftest_cells()):
        rep = infer_effects(src)
        if not rep.parsed or rep.opaque:
            bad.append(f"selftest cell {i}: {rep.opaque_reasons}")
    assert not bad, "\n".join(bad)


def test_hang_cell_footprint_nonempty_and_ordered():
    rep = infer_effects(HANG_CELL)
    assert rep.collectives, "HANG_CELL must carry a collective " \
                            "footprint"
    lines = [s.line for s in rep.collectives]
    assert lines == sorted(lines)
    assert collective_class(rep) != "free"


# ----------------------------------------------------------------------
# ISSUE 9 satellite: thread pass — gateway coverage + _locked helpers


def test_thread_pass_covers_gateway_files():
    from nbdistributed_tpu.analysis.selfcheck import \
        _THREAD_CHECKED_FILES
    covered = {os.path.basename(f) for f in _THREAD_CHECKED_FILES}
    assert {"daemon.py", "tenancy.py", "scheduler.py"} <= covered


def _locked_findings(src, method_name):
    tree = ast.parse(src)
    cls = tree.body[0]
    fn = [n for n in cls.body if isinstance(n, ast.FunctionDef)
          and n.name == method_name][0]
    p = _ThreadPass("x.py", cls.name, {"counts"}, {},
                    method=method_name)
    p.visit(fn)
    return p.findings


_LOCKED_SRC = """
class C:
    def __init__(self):
        self._lock = None
        self.counts = dict()
    def _bump_locked(self):
        self.counts['a'] = 1
        self.n += 1
    def unlocked_caller(self):
        self._bump_locked()
    def locked_caller(self):
        with self._lock:
            self._bump_locked()
"""


def test_locked_suffix_body_is_treated_as_locked():
    assert not _locked_findings(_LOCKED_SRC, "_bump_locked")


def test_unlocked_call_to_locked_helper_is_flagged():
    found = _locked_findings(_LOCKED_SRC, "unlocked_caller")
    assert found and "lock-asserting" in found[0].message


def test_locked_call_to_locked_helper_is_clean():
    assert not _locked_findings(_LOCKED_SRC, "locked_caller")


# ----------------------------------------------------------------------
# ISSUE 9: magic wiring — dispatched cells record effect footprints


def test_vet_cell_records_effects_on_dispatch(magic):
    from nbdistributed_tpu.runtime.collective_guard import cell_hash
    src = "ana_x = 1\nana_y = ana_x + free_read"
    assert magic._vet_cell(src, [0, 1]) is True
    entry = preflight.effects_for(cell_hash(src))
    assert entry is not None
    assert "ana_x" in entry["writes"] and "free_read" in entry["reads"]


def test_vet_cell_strict_block_records_nothing(magic):
    from nbdistributed_tpu.runtime.collective_guard import cell_hash
    assert magic._vet_cell(HANG_CELL, [0, 1], strict=True) is False
    assert preflight.effects_for(cell_hash(HANG_CELL)) is None


def test_vet_cell_unparseable_records_opaque(magic):
    from nbdistributed_tpu.runtime.collective_guard import cell_hash
    src = "def broken(:\npass"
    assert magic._vet_cell(src, [0, 1]) is True
    entry = preflight.effects_for(cell_hash(src))
    assert entry is not None and entry["opaque"]


def test_dist_lint_deps_and_effects_render(magic, capsys):
    magic._vet_cell("dag_a = 1", [0, 1])
    magic._vet_cell("dag_b = dag_a + 1", [0, 1])
    magic.dist_lint("deps")
    out = capsys.readouterr().out
    assert "dependency DAG" in out and "dag_a" in out
    magic.dist_lint("effects")
    out = capsys.readouterr().out
    assert "effect footprints" in out and "writes dag_b" in out


# ----------------------------------------------------------------------
# codec registry sanity (the table both the codec and self-lint import)


def test_wire_extensions_registry_shape():
    from nbdistributed_tpu.messaging.codec import (BASE_HEADER_KEYS,
                                                   WIRE_EXTENSIONS)
    assert {"at", "tr", "ep"} <= {
        k for k, v in WIRE_EXTENSIONS.items() if v["plane"] == "header"}
    assert {"col", "busy_s", "tel"} <= {
        k for k, v in WIRE_EXTENSIONS.items() if v["plane"] == "ping"}
    assert not set(WIRE_EXTENSIONS) & set(BASE_HEADER_KEYS)


# ======================================================================
# ISSUE 10: concurrency self-analysis (analysis/concur.py)


def _concur_results(tmp_path, src):
    """Run the three concurrency passes over one synthetic module in
    a throwaway product tree."""
    from nbdistributed_tpu.analysis.concur import run_concur_lint
    pkg = tmp_path / "nbdistributed_tpu"
    pkg.mkdir()
    (tmp_path / "tools").mkdir()
    (pkg / "mod.py").write_text(src)
    return run_concur_lint(str(tmp_path))


def _only(results, rule):
    """Assert exactly ``rule`` fired (the corpus contract: each
    synthetic violation must fire its rule and no other)."""
    for name, findings in results.items():
        if name == rule:
            assert findings, f"{rule} did not fire"
        else:
            assert findings == [], (
                f"[{name}] " + "; ".join(f.render() for f in findings))
    return results[rule]


_CYCLE_SRC = """
import threading

class A:
    def __init__(self):
        self._lock = threading.Lock()
        self._other_lock = threading.Lock()
    def fwd(self):
        with self._lock:
            with self._other_lock:
                pass
    def rev(self):
        with self._other_lock:
            with self._lock:
                pass
"""


def test_lock_order_cycle_fires_exactly_its_rule(tmp_path):
    found = _only(_concur_results(tmp_path, _CYCLE_SRC), "lock-order")
    assert any("cycle" in f.message and "A._lock" in f.message
               and "A._other_lock" in f.message for f in found)


_BURIED_CYCLE_SRC = """
import threading

class A:
    def __init__(self):
        self._a_lock = threading.Lock()
        self._b_lock = threading.Lock()
        self._c_lock = threading.Lock()
    def ab(self):
        with self._a_lock:
            with self._b_lock:
                pass
    def ac(self):
        with self._a_lock:
            with self._c_lock:
                pass
    def fwd(self):
        with self._b_lock:
            with self._c_lock:
                pass
    def rev(self):
        with self._c_lock:
            with self._b_lock:
                pass
"""


def test_lock_order_cycle_not_through_start_node_is_found(tmp_path):
    """A b↔c inversion reachable only THROUGH a third lock must still
    be reported — the SCC enumeration regression pin (a pruned
    DFS-from-each-start missed exactly this shape)."""
    found = _only(_concur_results(tmp_path, _BURIED_CYCLE_SRC),
                  "lock-order")
    assert any("cycle" in f.message and "A._b_lock" in f.message
               and "A._c_lock" in f.message for f in found)
    # The acyclic a→b / a→c prefix edges are NOT part of any finding.
    assert all("A._a_lock" not in f.message for f in found)


_REACQUIRE_SRC = """
import threading

class B:
    def __init__(self):
        self._lock = threading.{LOCK}()
    def outer(self):
        with self._lock:
            self._inner()
    def _inner(self):
        with self._lock:
            pass
"""


def test_plain_lock_reacquire_via_helper_is_a_deadlock(tmp_path):
    src = _REACQUIRE_SRC.replace("{LOCK}", "Lock")
    found = _only(_concur_results(tmp_path, src), "lock-order")
    assert any("already held" in f.message for f in found)


def test_rlock_reacquire_is_reentrant_and_clean(tmp_path):
    src = _REACQUIRE_SRC.replace("{LOCK}", "RLock")
    res = _concur_results(tmp_path, src)
    assert all(v == [] for v in res.values())


_SENDALL_SRC = """
import threading

class C:
    def __init__(self):
        self._lock = threading.Lock()
        self.sock = None
    def flush(self, frame):
        with self._lock:
            self.sock.sendall(frame)
"""


def test_sendall_under_lock_fires_exactly_its_rule(tmp_path):
    found = _only(_concur_results(tmp_path, _SENDALL_SRC),
                  "blocking-under-lock")
    assert "sendall" in found[0].message
    assert "C._lock" in found[0].message


def test_blocking_ok_exemption_table_silences_the_site(tmp_path):
    src = ('_LINT_BLOCKING_OK = {"C.flush:sendall": "frame-write '
           'serializer"}\n') + _SENDALL_SRC
    res = _concur_results(tmp_path, src)
    assert all(v == [] for v in res.values())


_CALLBACK_SRC = """
import threading

class D:
    def __init__(self):
        self._lock = threading.Lock()
        self.on_done = None
    def fire_direct(self):
        with self._lock:
            self.on_done(1)
    def fire_alias(self):
        with self._lock:
            cb = self.on_done
            cb(2)
    def fire_outside(self):
        with self._lock:
            cb = self.on_done
        cb(3)
"""


def test_callback_under_lock_fires_exactly_its_rule(tmp_path):
    found = _only(_concur_results(tmp_path, _CALLBACK_SRC),
                  "callback-under-lock")
    # Direct invocation and the locked alias fire; the copy-then-
    # invoke-outside pattern (the documented fix) is clean.
    lines = sorted(f.line for f in found)
    assert len(found) == 2
    assert all("on_done" in f.message or "cb" in f.message
               for f in found)
    src_lines = _CALLBACK_SRC.splitlines()
    assert all("fire_outside" not in src_lines[ln - 2]
               for ln in lines)


def test_callback_ok_exemption_table_silences_the_site(tmp_path):
    src = ('_LINT_CALLBACK_OK = {"D.fire_direct:on_done": "reentry-'
           'safe by contract", "D.fire_alias:cb": "ditto"}\n'
           ) + _CALLBACK_SRC
    res = _concur_results(tmp_path, src)
    assert all(v == [] for v in res.values())


_LOCKED_HELPER_SRC = """
import threading
import time

class E:
    def __init__(self):
        self._lock = threading.Lock()
    def _flush_locked(self):
        time.sleep(1)
"""


def test_locked_suffix_asserts_entry_lockset(tmp_path):
    found = _only(_concur_results(tmp_path, _LOCKED_HELPER_SRC),
                  "blocking-under-lock")
    assert "time.sleep" in found[0].message
    assert "E._lock" in found[0].message


def test_locked_helper_defect_reported_once_not_per_caller(tmp_path):
    """One blocking op in a `_locked` helper with k locked callers is
    ONE defect: the helper self-reports via its entry lockset, and
    via-resolution must not re-flag it at every call site."""
    src = _LOCKED_HELPER_SRC + """
    def caller_one(self):
        with self._lock:
            self._flush_locked()
    def caller_two(self):
        with self._lock:
            self._flush_locked()
"""
    found = _only(_concur_results(tmp_path, src),
                  "blocking-under-lock")
    assert len(found) == 1
    assert found[0].message.startswith("E._flush_locked:")


_VIA_HELPER_SRC = """
import threading

class F:
    def __init__(self):
        self._lock = threading.Lock()
        self.ch = None
    def caller(self):
        with self._lock:
            self._emit()
    def _emit(self):
        self.ch.sendall(b"x")
"""


def test_one_level_resolution_flags_blocking_via_helper(tmp_path):
    found = _only(_concur_results(tmp_path, _VIA_HELPER_SRC),
                  "blocking-under-lock")
    assert "via F._emit" in found[0].message
    # The finding anchors at the locked CALL site, not inside the
    # (lock-free when called alone) helper.
    assert found[0].line == _VIA_HELPER_SRC.splitlines().index(
        "            self._emit()") + 1


_ACQUIRE_RELEASE_SRC = """
import threading
import time

class G:
    def __init__(self):
        self._lock = threading.Lock()
    def run(self):
        self._lock.acquire()
        time.sleep(1)
        self._lock.release()
        time.sleep(2)
"""


def test_acquire_release_pairs_scope_the_lockset(tmp_path):
    found = _only(_concur_results(tmp_path, _ACQUIRE_RELEASE_SRC),
                  "blocking-under-lock")
    assert len(found) == 1   # only the sleep between acquire/release
    assert found[0].line == _ACQUIRE_RELEASE_SRC.splitlines().index(
        "        time.sleep(1)") + 1


def test_module_level_lock_is_tracked(tmp_path):
    src = """
import threading
import time

_lock = threading.Lock()

def flush():
    with _lock:
        time.sleep(1)
"""
    found = _only(_concur_results(tmp_path, src),
                  "blocking-under-lock")
    assert "mod::_lock" in found[0].message


def test_non_lock_attrs_never_participate(tmp_path):
    # "block" in the name is not enough — only attributes proven to
    # be Lock()/RLock()/Condition() constructions count.
    src = """
import time

class H:
    def __init__(self):
        self.blocker = object()
    def run(self):
        with self.blocker:
            time.sleep(1)
"""
    res = _concur_results(tmp_path, src)
    assert all(v == [] for v in res.values())


def test_lock_graph_dot_contains_real_edges():
    from nbdistributed_tpu.analysis.concur import lock_graph_dot
    dot = lock_graph_dot(REPO)
    assert dot.startswith("digraph lock_order")
    # The daemon parks/claims mailbox results under its lock — the
    # cross-class edge the attr-type registry resolves.
    assert '"GatewayDaemon._lock" -> "ResultMailbox._mlock"' in dot
    # Reentrant self-edges (RLock helper convention) are drawn dashed,
    # documenting the re-entry rather than flagging it.
    assert "style=dashed" in dot


# ----------------------------------------------------------------------
# ISSUE 10 satellite: protocol handler coverage


def test_protocol_coverage_synthetic_both_directions():
    from nbdistributed_tpu.analysis.selfcheck import \
        check_protocol_coverage
    planes = [{"name": "x",
               "sent": {"a": ("f.py", 1), "b": ("f.py", 2)},
               "handled": {"a": ("g.py", 3), "c": ("g.py", 4)}}]
    found = check_protocol_coverage(REPO, planes=planes, external={})
    msgs = [f.message for f in found]
    assert len(found) == 2
    assert any("'b' is sent here but no receiver handles" in m
               for m in msgs)
    assert any("'c' is registered here but nothing" in m for m in msgs)
    # Exemptions silence both directions.
    assert check_protocol_coverage(
        REPO, planes=planes,
        external={"x:b": "why", "x:c": "why"}) == []


def test_protocol_planes_cover_the_real_wire():
    from nbdistributed_tpu.analysis.selfcheck import _protocol_planes
    planes = {p["name"]: p for p in _protocol_planes(REPO)}
    assert {"worker", "worker-notice", "tenant", "tenant-notice",
            "agent", "agent-notice"} <= set(planes)
    assert {"execute", "shutdown", "tenant_gc"} <= set(
        planes["worker"]["sent"])
    assert {"execute", "shutdown", "tenant_gc"} <= set(
        planes["worker"]["handled"])
    assert {"tenant_hello", "execute", "mailbox", "detach"} <= set(
        planes["tenant"]["sent"])
    assert {"queued", "parked_notice", "stream_output",
            # ISSUE 11: the serving plane's pushes (serving.py) are
            # tenant-plane notices too.
            "serve_tokens", "serve_done",
            # ISSUE 16: tenant_import reconstructs migrated parked
            # results as "response"-typed mailbox entries; they only
            # ever leave inside a mailbox drain (exempted in
            # _PROTOCOL_EXTERNAL).
            "response"} == set(
        planes["tenant-notice"]["sent"])
    assert {"serve_submit", "serve_result", "serve_stream",
            "serve_start", "serve_status", "serve_stop"} <= set(
        planes["tenant"]["sent"])
    assert {"serve_open", "serve_step", "serve_close"} <= set(
        planes["worker"]["handled"])
    assert {"spawn", "signal", "tail", "reap", "poll"} <= set(
        planes["agent"]["sent"])


# ----------------------------------------------------------------------
# ISSUE 10 satellite: CLI modes — dot exports, JSON format, exit codes


def test_cli_exit_codes_pinned(tmp_path, capsys):
    from nbdistributed_tpu.analysis.cli import main
    # 2: no mode selected (help), unreadable file, --deps-dot sans
    # files.
    assert main([]) == 2
    capsys.readouterr()
    assert main([str(tmp_path / "missing.py")]) == 2
    capsys.readouterr()
    assert main(["--deps-dot"]) == 2
    capsys.readouterr()
    # 0: clean checkout self-lint; clean file.
    assert main(["--self", "--root", REPO]) == 0
    capsys.readouterr()
    ok = tmp_path / "ok.py"
    ok.write_text("x = 1\n")
    assert main([str(ok)]) == 0
    capsys.readouterr()
    # 1: error-severity cell finding.
    bad = tmp_path / "bad.py"
    bad.write_text(HANG_CELL)
    assert main([str(bad)]) == 1
    capsys.readouterr()
    # Highest code wins regardless of argument order: unreadable (2)
    # beats findings (1) in both positions.
    missing = str(tmp_path / "missing.py")
    assert main([missing, str(bad)]) == 2
    capsys.readouterr()
    assert main([str(bad), missing]) == 2
    capsys.readouterr()
    # Unparseable: 0 by the never-block contract, 1 under --strict
    # (an uninspectable cell cannot be called clean there).
    broken = tmp_path / "broken.py"
    broken.write_text("def broken(:\n    pass\n")
    assert main([str(broken)]) == 0
    capsys.readouterr()
    assert main([str(broken), "--strict"]) == 1
    out = capsys.readouterr().out
    assert "FAILED under --strict" in out


def test_cli_json_format_self_and_files(tmp_path, capsys):
    from nbdistributed_tpu.analysis.cli import main
    assert main(["--self", "--root", REPO, "--format", "json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["mode"] == "self" and doc["total"] == 0
    assert doc["exit_code"] == 0
    assert set(doc["passes"]) >= {"lock-order", "blocking-under-lock",
                                  "callback-under-lock",
                                  "protocol-coverage"}
    bad = tmp_path / "bad.py"
    bad.write_text(HANG_CELL)
    assert main([str(bad), "--format", "json"]) == 1
    doc = json.loads(capsys.readouterr().out)
    assert doc["mode"] == "files" and doc["exit_code"] == 1
    (entry,) = doc["files"].values()
    assert entry["parsed"] is True
    assert any(f["rule"] == "rank-conditional-collective"
               and f["severity"] == "error"
               for f in entry["findings"])


def test_cli_lock_graph_and_deps_dot(tmp_path, capsys):
    from nbdistributed_tpu.analysis.cli import main
    assert main(["--lock-graph", "--root", REPO]) == 0
    assert capsys.readouterr().out.startswith("digraph lock_order")
    a = tmp_path / "a.py"
    b = tmp_path / "b.py"
    a.write_text("x = 1\n")
    b.write_text("y = x + 1\n")
    assert main(["--deps-dot", str(a), str(b)]) == 0
    out = capsys.readouterr().out
    assert out.startswith("digraph cell_deps")
    assert '"c0" -> "c1"' in out and 'label="x"' in out


def test_dag_to_dot_marks_opaque_cells():
    from nbdistributed_tpu.analysis.effects import infer_effects
    from nbdistributed_tpu.analysis.preflight import (dag_from_entries,
                                                      dag_to_dot)
    entries = []
    for seq, src in enumerate(["a = 1", "exec('a = 2')", "b = a"]):
        e = {"seq": seq, "sha": f"s{seq}"}
        e.update(infer_effects(src).as_dict())
        entries.append(e)
    dag = dag_from_entries(entries)
    dot = dag_to_dot(dag)
    assert "fillcolor" in dot          # the opaque exec cell
    # Opaque cells gate everything: both neighbors connect to c1.
    assert '"c0" -> "c1"' in dot and '"c1" -> "c2"' in dot


def test_dist_lint_deps_dot_renders(magic, capsys):
    magic._vet_cell("dot_a = 1", [0, 1])
    magic._vet_cell("dot_b = dot_a + 1", [0, 1])
    magic.dist_lint("deps --dot")
    out = capsys.readouterr().out
    assert out.strip().startswith("digraph cell_deps")
    assert "->" in out


# ======================================================================
# ISSUE 15: lifecycle lint (analysis/lifecycle.py) — synthetic corpus
# (per rule: one sample firing exactly that rule, and a clean twin)


def _lifecycle_results(tmp_path, src):
    """Run the three lifecycle passes over one synthetic module in a
    throwaway product tree (the _concur_results analog)."""
    from nbdistributed_tpu.analysis.lifecycle import run_lifecycle_lint
    pkg = tmp_path / "nbdistributed_tpu"
    pkg.mkdir(parents=True)
    (tmp_path / "tools").mkdir()
    (pkg / "mod.py").write_text(src)
    return run_lifecycle_lint(str(tmp_path))


def _lifecycle_clean(tmp_path, src):
    res = _lifecycle_results(tmp_path, src)
    assert all(v == [] for v in res.values()), {
        k: [f.render() for f in v] for k, v in res.items() if v}


# -- resource-leak ------------------------------------------------------


def test_leak_socket_never_released_fires(tmp_path):
    found = _only(_lifecycle_results(tmp_path, """
import socket

def probe(host):
    s = socket.create_connection((host, 80))
    s.sendall(b"x")
"""), "resource-leak")
    assert "never released" in found[0].message
    assert "socket" in found[0].message


def test_leak_release_only_on_fall_through_fires(tmp_path):
    found = _only(_lifecycle_results(tmp_path, """
import socket

def probe(host):
    s = socket.create_connection((host, 80))
    s.sendall(b"x")
    s.close()
"""), "resource-leak")
    assert "fall-through" in found[0].message


def test_leak_clean_twins_with_block_and_finally(tmp_path):
    _lifecycle_clean(tmp_path, """
import socket

def probe_with(host):
    with socket.create_connection((host, 80)) as s:
        s.sendall(b"x")

def probe_finally(host):
    s = socket.create_connection((host, 80))
    try:
        s.sendall(b"x")
    finally:
        s.close()

def make_and_close():
    s = socket.socket()
    s.close()
""")


def test_leak_ownership_transfer_clean_twins(tmp_path):
    _lifecycle_clean(tmp_path, """
import socket

def returned():
    s = socket.socket()
    return s

def registered(registry):
    s = socket.socket()
    registry.register(s)

class Owner:
    def __init__(self):
        self.sock = None
    def arm(self, host):
        s = socket.create_connection((host, 80))
        self.sock = s
    def close(self):
        self.sock.close()
""")


def test_leak_nondaemon_thread_fires_daemon_clean(tmp_path):
    found = _only(_lifecycle_results(tmp_path, """
import threading

def run(fn):
    t = threading.Thread(target=fn)
    t.start()
"""), "resource-leak")
    assert "thread" in found[0].message
    _lifecycle_clean(tmp_path / "d", """
import threading

def run(fn):
    t = threading.Thread(target=fn, daemon=True)
    t.start()

def run_joined(fn):
    t = threading.Thread(target=fn)
    t.start()
    try:
        pass
    finally:
        t.join()
""")


def test_leak_popen_and_write_open(tmp_path):
    found = _only(_lifecycle_results(tmp_path, """
import subprocess

def launch(argv):
    p = subprocess.Popen(argv)
    p.poll()
"""), "resource-leak")
    assert "process" in found[0].message
    # Read-mode open is not in the acquire vocabulary; adjacent
    # wait() is a zero-raise-window release.
    _lifecycle_clean(tmp_path / "c", """
import subprocess

def launch(argv):
    p = subprocess.Popen(argv)
    p.wait()

def read(path):
    f = open(path)
    return f
""")


def test_leak_socketpair_each_end_needs_its_own_release(tmp_path):
    # Closing one end must not satisfy the check for the other.
    found = _only(_lifecycle_results(tmp_path, """
import socket

def pair():
    r, w = socket.socketpair()
    r.close()
"""), "resource-leak")
    assert len(found) == 1 and "'w'" in found[0].message
    _lifecycle_clean(tmp_path / "c", """
import socket

def pair():
    r, w = socket.socketpair()
    try:
        pass
    finally:
        r.close()
        w.close()
""")


def test_leak_exemption_table_silences_the_site(tmp_path):
    _lifecycle_clean(tmp_path, """
_LINT_LIFECYCLE_OK = {"probe:socket": "one-shot probe; the process "
                      "exits right after and the OS reclaims the fd"}
import socket

def probe(host):
    s = socket.create_connection((host, 80))
    s.sendall(b"x")
""")


# -- bracket-discipline -------------------------------------------------


_SERVE_BRACKET_HEAD = """
import threading

class G:
    def __init__(self):
        self._lock = threading.Lock()
        self._serving = {}
    def _serve_done(self, name):
        with self._lock:
            self._serving[name] = self._serving.get(name, 1) - 1
"""


def test_bracket_serve_slot_unprotected_fires(tmp_path):
    found = _only(_lifecycle_results(tmp_path, _SERVE_BRACKET_HEAD + """
    def submit(self, name):
        with self._lock:
            self._serving[name] = self._serving.get(name, 0) + 1
        self.do_work(name)
"""), "bracket-discipline")
    assert "serve-slot" in found[0].message


def test_bracket_serve_slot_thread_handoff_clean(tmp_path):
    _lifecycle_clean(tmp_path, _SERVE_BRACKET_HEAD + """
    def submit(self, name):
        with self._lock:
            self._serving[name] = self._serving.get(name, 0) + 1
        threading.Thread(target=self._serve, args=(name,),
                         daemon=True).start()
    def _serve(self, name):
        try:
            self.work(name)
        finally:
            self._serve_done(name)
    def submit_inline(self, name):
        with self._lock:
            self._serving[name] = self._serving.get(name, 0) + 1
        try:
            self.work(name)
        finally:
            self._serve_done(name)
""")


def test_bracket_mailbox_claim_fires_and_repark_twin_clean(tmp_path):
    found = _only(_lifecycle_results(tmp_path, """
class W:
    def drain(self, box, reply):
        claimed = box.claim_all()
        return reply(claimed)
"""), "bracket-discipline")
    assert "mailbox-claim" in found[0].message
    _lifecycle_clean(tmp_path / "c", """
class W:
    def drain(self, box, reply):
        claimed = box.claim_all()
        try:
            return reply(claimed)
        except Exception:
            for mid, r in claimed.items():
                box.park(mid, r)
            raise
""")


def test_bracket_gauge_updown_fires_only_with_dec_in_module(tmp_path):
    found = _only(_lifecycle_results(tmp_path, """
class M:
    def __init__(self):
        self.g = None
    def enter(self):
        self.g.inc()
        self.work()
    def leave(self):
        self.g.dec()
"""), "bracket-discipline")
    assert "gauge-updown" in found[0].message
    # Monotonic counters (inc with no dec anywhere in the module)
    # never arm the bracket…
    _lifecycle_clean(tmp_path / "mono", """
class M:
    def count(self, c):
        c.inc()
        self.work()
""")
    # …nor does a dec on a DIFFERENT receiver arm someone else's
    # counter inc (pairing is per dotted receiver).
    _lifecycle_clean(tmp_path / "other", """
class M:
    def __init__(self):
        self.g = None
        self.requests = None
    def count(self):
        self.requests.inc()
        self.work()
    def leave(self):
        self.g.dec()
""")
    # …and the finally twin is clean even with dec present.
    _lifecycle_clean(tmp_path / "c", """
class M:
    def __init__(self):
        self.g = None
    def enter(self):
        self.g.inc()
        try:
            self.work()
        finally:
            self.g.dec()
""")


def test_bracket_exemption_table_silences_the_site(tmp_path):
    _lifecycle_clean(tmp_path, """
_LINT_LIFECYCLE_OK = {"W.drain:mailbox-claim": "the completion "
                      "callback reparks on failure by contract"}

class W:
    def drain(self, box, reply):
        claimed = box.claim_all()
        return reply(claimed)
""")


# -- shutdown-completeness ----------------------------------------------


def test_shutdown_unreleased_socket_fires_release_twin_clean(tmp_path):
    found = _only(_lifecycle_results(tmp_path, """
import socket

class S:
    def __init__(self):
        self._sock = socket.create_connection(("h", 1))
    def close(self):
        pass
"""), "shutdown-completeness")
    assert "_sock" in found[0].message
    _lifecycle_clean(tmp_path / "c", """
import socket

class S:
    def __init__(self):
        self._sock = socket.create_connection(("h", 1))
    def close(self):
        self._sock.close()
""")


def test_shutdown_no_surface_at_all_fires(tmp_path):
    found = _only(_lifecycle_results(tmp_path, """
import socket

class S:
    def __init__(self):
        self._sock = socket.socket()
"""), "shutdown-completeness")
    assert "defines no close" in found[0].message


def test_shutdown_nondaemon_thread_must_be_joined(tmp_path):
    found = _only(_lifecycle_results(tmp_path, """
import threading

class S:
    def __init__(self):
        self._t = threading.Thread(target=self._run)
        self._t.start()
    def _run(self):
        pass
    def close(self):
        pass
"""), "shutdown-completeness")
    assert "non-daemon thread" in found[0].message


def test_shutdown_daemon_thread_lock_hazard_and_join_twin(tmp_path):
    found = _only(_lifecycle_results(tmp_path, """
import threading

class S:
    def __init__(self):
        self._lock = threading.Lock()
        self._t = threading.Thread(target=self._run, daemon=True)
    def _run(self):
        with self._lock:
            pass
    def close(self):
        pass
"""), "shutdown-completeness")
    assert "interpreter teardown" in found[0].message
    _lifecycle_clean(tmp_path / "joined", """
import threading

class S:
    def __init__(self):
        self._lock = threading.Lock()
        self._t = threading.Thread(target=self._run, daemon=True)
    def _run(self):
        with self._lock:
            pass
    def close(self):
        self._t.join(timeout=1.0)
""")
    # A daemon thread that touches no lock needs no surface at all.
    _lifecycle_clean(tmp_path / "harmless", """
import threading

class S:
    def __init__(self):
        self._t = threading.Thread(target=self._run, daemon=True)
    def _run(self):
        pass
""")


def test_shutdown_owner_typed_attr_and_alias_release(tmp_path):
    found = _only(_lifecycle_results(tmp_path, """
import socket

class Inner:
    def __init__(self):
        self._sock = socket.socket()
    def close(self):
        self._sock.close()

class Outer:
    def __init__(self):
        self._inner = Inner()
    def close(self):
        pass
"""), "shutdown-completeness")
    assert "Inner" in found[0].message and "_inner" in found[0].message
    # The swap-then-close alias (`s, self._sock = self._sock, None`)
    # and the close-loop over a tuple of attrs both count as releases.
    _lifecycle_clean(tmp_path / "alias", """
import socket

class S:
    def __init__(self):
        self._sock = socket.socket()
        self._wake_r, self._wake_w = socket.socketpair()
    def close(self):
        s, self._sock = self._sock, None
        s.close()
        for w in (self._wake_r, self._wake_w):
            w.close()
""")


def test_shutdown_exemption_table_silences_the_attr(tmp_path):
    _lifecycle_clean(tmp_path, """
_LINT_LIFECYCLE_OK = {"S:_sock": "held for the process lifetime by "
                      "design (faulthandler-style registration)"}
import socket

class S:
    def __init__(self):
        self._sock = socket.socket()
""")


def test_shutdown_ledger_report_shape():
    from nbdistributed_tpu.analysis.lifecycle import shutdown_ledger
    ledger = shutdown_ledger(REPO)
    # Real owners with their release evidence…
    tc = ledger["TenantClient"]
    assert tc["file"] == "nbdistributed_tpu/gateway/client.py"
    reader = {r["attr"]: r for r in tc["resources"]}["_reader"]
    assert reader["daemon"] and "join" in reader["released_by"]
    # …and the worker's exemption-tabled faulthandler fd carries its
    # reason.
    w = ledger["DistributedWorker"]
    stack = {r["attr"]: r for r in w["resources"]}["_stack_file"]
    assert stack["exempt"] and "faulthandler" in stack["exempt"]
    json.dumps(ledger)   # CI artifact: must be JSON-serializable


# ----------------------------------------------------------------------
# ISSUE 15 satellite: SARIF output (one run, rule ids = pass names)


def test_cli_sarif_self_mode_validates(capsys):
    from nbdistributed_tpu.analysis.cli import main
    assert main(["--self", "--root", REPO, "--format", "sarif"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["version"] == "2.1.0"
    assert "sarif-2.1.0" in doc["$schema"]
    (run,) = doc["runs"]
    assert run["tool"]["driver"]["name"] == "nbd-lint"
    ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert {"resource-leak", "bracket-discipline",
            "shutdown-completeness", "lock-order", "env-knobs",
            "protocol-coverage"} <= ids
    assert run["results"] == []        # the clean-checkout pin again


def test_cli_sarif_file_mode_findings_and_exit_codes(tmp_path, capsys):
    from nbdistributed_tpu.analysis.cli import main
    bad = tmp_path / "bad.py"
    bad.write_text(HANG_CELL)
    assert main([str(bad), "--format", "sarif"]) == 1
    doc = json.loads(capsys.readouterr().out)
    (res,) = doc["runs"][0]["results"]
    assert res["ruleId"] == "rank-conditional-collective"
    assert res["level"] == "error"
    loc = res["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"].endswith("bad.py")
    assert loc["region"]["startLine"] == 5      # stable location
    # Unparseable input: visible as a note, exit 0 by the
    # never-block contract — but a warning AND exit 1 under --strict.
    broken = tmp_path / "broken.py"
    broken.write_text("def broken(:\n")
    assert main([str(broken), "--format", "sarif"]) == 0
    doc = json.loads(capsys.readouterr().out)
    (res,) = doc["runs"][0]["results"]
    assert res["ruleId"] == "not-analyzable" and res["level"] == "note"
    assert main([str(broken), "--format", "sarif", "--strict"]) == 1
    doc = json.loads(capsys.readouterr().out)
    assert doc["runs"][0]["results"][0]["level"] == "warning"


def test_cli_shutdown_ledger_mode(capsys):
    from nbdistributed_tpu.analysis.cli import main
    assert main(["--shutdown-ledger", "--root", REPO]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert "CoordinatorListener" in doc
    attrs = {r["attr"] for r in doc["CoordinatorListener"]["resources"]}
    assert {"_server", "_wake_r", "_wake_w"} <= attrs


# ----------------------------------------------------------------------
# ISSUE 15 satellite: %dist_lint self parity with the CLI


def test_dist_lint_self_reports_all_pass_counts(magic, capsys):
    magic.dist_lint("self")
    out = capsys.readouterr().out
    for name in ("env-knobs", "codec-headers", "thread-shared-state",
                 "protocol-coverage", "lock-order",
                 "blocking-under-lock", "callback-under-lock",
                 "resource-leak", "bracket-discipline",
                 "shutdown-completeness"):
        assert f"{name}: clean" in out, name
    assert "all passes clean" in out
