"""README.md and PERF.md against the tree: what they name exists. ROADMAP.md is
left out: its re-anchor session runs no tests and may name files to come."""

import glob
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PATH_PREFIXES = ("daft_tpu/", "benchmark/", "benchmarking/", "tests/",
                 "tests_tpu/", "native/")
ROOT_FILE = re.compile(r"[\w.\-*]+\.(py|md|jsonl?)")

# Names a document may write although git holds no such file: README's are
# names the reader chooses (a trace, an event log, a dump handed to doctor or
# calibrate) and the library built on first use; a traced run writes PERF.md's.
NOT_FILES = {
    "README.md": {"trace.json", "events.jsonl", "DUMP.json", "FILE.json",
                  "daft_tpu/_native/libdaft_native.so"},
    "PERF.md": {"reduced.json"},
}


def _read(name):
    with open(os.path.join(REPO, name)) as f:
        return f.read()


def _python_sources():
    return "\n".join(_read(p) for p in glob.glob(
        os.path.join(REPO, "daft_tpu", "**", "*.py"), recursive=True))


def _named_paths(text):
    """Backticked names that start with a source directory or look like a file
    of the root; a trailing :line or :a-b is cut, <...> and dot-dirs skipped."""
    for tick in re.findall(r"`([^`\n]+)`", text):
        name = re.sub(r":\d+(-\d+)?(,\d+(-\d+)?)*$", "", tick.strip())
        if "<" in name or " " in name or name.startswith("."):
            continue
        if name.startswith(PATH_PREFIXES) or ROOT_FILE.fullmatch(name):
            yield name


@pytest.mark.parametrize("doc", ["README.md", "PERF.md"])
def test_named_files_exist(doc):
    # a bare name is a root file, or a module called by its last component
    basenames = set()
    for _, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".") and d != "chiprun_out"]
        basenames.update(files)
    missing = sorted({
        n for n in _named_paths(_read(doc))
        if n not in NOT_FILES[doc] and n not in basenames
        and not glob.glob(os.path.join(REPO, n.rstrip("/")))})
    assert not missing, f"{doc} names files that do not exist: {missing}"


def test_readme_make_targets_exist():
    targets = set(re.findall(r"^([\w\-]+):", _read("Makefile"), re.M))
    named = set(re.findall(r"`make ([\w\-]+)", _read("README.md")))
    assert named and named <= targets, sorted(named - targets)


@pytest.mark.parametrize("doc", ["README.md", "PERF.md"])
def test_named_env_variables_are_read(doc):
    src = _python_sources()
    named = set(re.findall(r"DAFT_TPU_[A-Z0-9_]+", _read(doc)))
    # a name ending in "_" is a prefix family (DAFT_TPU_TENANT_WEIGHT_<tenant>)
    unread = sorted(n for n in named if not re.search(
        n + ("" if n.endswith("_") else "(?![A-Z0-9_])"), src))
    assert not unread, f"{doc} names variables nothing under daft_tpu/ reads: {unread}"


def perf_md_spans_and_counters():
    """The names in the first column of PERF.md section 3's table of spans and
    counters (`a.b/c` gives `a.b` and `a.c`; what stands in brackets is left
    out)."""
    text = _read("PERF.md")
    table = text[text.index("| Span or counter | Site |"):]
    table = table[:table.index("\n\n")]
    names = []
    for row in table.split("\n")[2:]:
        first = re.sub(r"\([^()]*\)", "", row.split("|")[1])
        for tick in re.findall(r"`([^`]+)`", first):
            head, *alts = tick.split("/")
            stem = head[:head.rfind(".") + 1]
            names += [head] + [stem + a for a in alts]
    return names


def test_perf_md_spans_and_counters_exist():
    names = perf_md_spans_and_counters()
    src = _python_sources()
    assert len(names) > 40, names
    missing = []
    for n in names:
        prefix = re.split(r"[*<]", n)[0]      # `op.<Node>`, `device.udf_*`
        if not re.search(r"[\"']" + re.escape(prefix) + (r"[\"']" if prefix == n else ""), src):
            missing.append(n)
    assert not missing, f"PERF.md §3 names what daft_tpu/ never emits: {missing}"


def _run(*argv):
    return subprocess.run([sys.executable, "-m", *argv], capture_output=True,
                          text=True, cwd=REPO, timeout=120,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"})


def test_cli_subcommands():
    out = _run("daft_tpu", "--help")
    assert out.returncode == 0, out.stderr
    assert re.search(r"\{([\w,]+)\}", out.stdout).group(1) == "info,sql,schema"
    assert _run("daft_tpu", "bench").returncode == 2


def test_doctor_usage_names_no_compare():
    out = _run("daft_tpu.tools.doctor", "--help")
    assert out.returncode == 0
    assert "DUMP.json" in out.stderr and "--compare" not in out.stderr
