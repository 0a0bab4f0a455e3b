"""putpu-lint (ISSUE 6): per-checker positive/negative fixtures, waiver
parsing, baseline suppression — and the meta-invariant that the
committed tree itself lints clean.

Fixture snippets are compiled from strings (never from repo files) with
virtual ``pulsarutils_tpu/...`` paths so the layer-scoped checkers see
the package layout without depending on it.  The linter is stdlib-only;
no JAX backend is touched anywhere in this module.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from pulsarutils_tpu.analysis import (LintProject, lint_source,
                                      load_baseline, save_baseline)
from pulsarutils_tpu.analysis import baseline as baseline_mod
from pulsarutils_tpu.analysis import waivers as waivers_mod
from pulsarutils_tpu.analysis.cli import run_lint
from pulsarutils_tpu.obs import names

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

OPS = "pulsarutils_tpu/ops/fixture.py"
PAR = "pulsarutils_tpu/parallel/fixture.py"
OBS = "pulsarutils_tpu/obs/fixture.py"


def ids(findings):
    return sorted(f.checker for f in findings)


def lint(src, path=OPS, **kw):
    return lint_source(textwrap.dedent(src), path=path, **kw)


# -- checker 1: retrace hazards ----------------------------------------------

def test_retrace_shard_map_import_fires_outside_mesh():
    src = "from jax.experimental.shard_map import shard_map\n"
    assert ids(lint(src, path=PAR)) == ["retrace-shard-map"]


def test_retrace_shard_map_attribute_fires():
    src = "import jax\nf = jax.shard_map\n"
    assert "retrace-shard-map" in ids(lint(src, path=PAR))


def test_retrace_shard_map_silent_in_mesh_home():
    src = "from jax.experimental.shard_map import shard_map\n"
    assert lint(src, path="pulsarutils_tpu/parallel/mesh.py") == []


def test_retrace_shard_map_compat_is_sanctioned():
    src = """\
    from pulsarutils_tpu.parallel.mesh import shard_map_compat
    fn = shard_map_compat(lambda x: x, mesh=None, in_specs=(),
                          out_specs=())
    """
    assert "retrace-shard-map" not in ids(lint(src, path=PAR))


def test_retrace_jit_in_loop_fires():
    src = """\
    import jax
    def run(chunks, g, x):
        for c in chunks:
            f = jax.jit(g)
            f(x)
    """
    assert ids(lint(src)) == ["retrace-jit-in-loop"]


def test_retrace_jit_hoisted_is_silent():
    src = """\
    import jax
    def run(chunks, g, x):
        f = jax.jit(g)
        for c in chunks:
            f(x)
    """
    assert lint(src) == []


def test_retrace_static_unhashable_default_fires():
    src = """\
    import jax
    def kern(x, opts=[]):
        return x
    fast = jax.jit(kern, static_argnums=(1,))
    """
    assert ids(lint(src)) == ["retrace-static-unhashable"]


def test_retrace_static_unhashable_decorator_form_fires():
    src = """\
    import functools, jax
    @functools.partial(jax.jit, static_argnames=("plan",))
    def kern(x, plan={}):
        return x
    """
    assert ids(lint(src)) == ["retrace-static-unhashable"]


def test_retrace_static_hashable_default_is_silent():
    src = """\
    import jax
    def kern(x, opts=()):
        return x
    fast = jax.jit(kern, static_argnums=(1,))
    """
    assert lint(src) == []


# -- checker 2: undeclared device trip ---------------------------------------

DEVICE_READBACK = """\
import numpy as np
import jax.numpy as jnp
def readback(x):
    y = jnp.sum(x * 2)
    return np.asarray(y)
"""


def test_device_trip_unattributed_asarray_fires():
    assert ids(lint(DEVICE_READBACK)) == ["device-trip"]


def test_device_trip_silent_inside_budget_bucket():
    src = """\
    import numpy as np
    import jax.numpy as jnp
    from pulsarutils_tpu.utils.logging_utils import budget_bucket
    def readback(x):
        y = jnp.sum(x * 2)
        with budget_bucket("search/readback"):
            return np.asarray(y)
    """
    assert lint(src) == []


def test_device_trip_silent_outside_device_layers():
    # obs/ is host-side by construction; the checker scopes to
    # ops/ + parallel/
    assert lint(DEVICE_READBACK, path=OBS) == []


def test_device_trip_silent_in_pure_host_function():
    src = """\
    import numpy as np
    def plan(dms):
        return np.asarray(dms, dtype=np.float32)
    """
    assert lint(src) == []


def test_device_trip_host_fixpoint_chain_is_silent():
    # host-ness chains through assignments: np result -> method call
    src = """\
    import numpy as np
    import jax.numpy as jnp
    def offsets(x):
        y = jnp.sum(x)
        shifts = np.rint([1.0, 2.0])
        return int(shifts.max()), y
    """
    assert lint(src) == []


def test_device_trip_item_fires_block_until_ready_fires():
    src = """\
    import jax.numpy as jnp
    def wait(x):
        y = jnp.sum(x)
        y.block_until_ready()
        return y.item()
    """
    assert ids(lint(src)) == ["device-trip", "device-trip"]


def test_device_trip_param_scalar_coercion_is_silent():
    src = """\
    import jax.numpy as jnp
    def plan(x, nchan):
        n = int(nchan)
        return jnp.zeros((n,))
    """
    assert lint(src) == []


def test_device_trip_sanctioned_seam_is_silent():
    src = """\
    import numpy as np
    import jax.numpy as jnp
    def fetch_global(x):
        return np.asarray(jnp.sum(x))
    """
    assert lint(src) == []


# -- checker 3: lock discipline ----------------------------------------------

LOCKED_CLASS = """\
import threading
class Registry:
    def __init__(self):
        self._lock = threading.Lock()
        self.items = []
        self.count = 0
    %s
"""


def test_lock_discipline_unlocked_mutation_fires():
    src = LOCKED_CLASS % textwrap.dedent("""\
    def add(self, x):
            self.items.append(x)
            self.count += 1
    """)
    assert ids(lint(src, path=OBS)) == ["lock-discipline",
                                        "lock-discipline"]


def test_lock_discipline_locked_mutation_is_silent():
    src = LOCKED_CLASS % textwrap.dedent("""\
    def add(self, x):
            with self._lock:
                self.items.append(x)
                self.count += 1
    """)
    assert lint(src, path=OBS) == []


def test_lock_discipline_init_is_exempt():
    assert lint(LOCKED_CLASS % "pass\n", path=OBS) == []


def test_lock_discipline_unmarked_class_is_silent():
    src = """\
    class Plain:
        def __init__(self):
            self.items = []
        def add(self, x):
            self.items.append(x)
    """
    assert lint(src, path=OBS) == []


def test_lock_discipline_helper_called_under_lock_is_silent():
    # the HealthEngine._raise pattern: private helper, every call site
    # holds the lock -> its mutations inherit the caller's scope
    src = LOCKED_CLASS % textwrap.dedent("""\
    def add(self, x):
            with self._lock:
                self._bump(x)

        def _bump(self, x):
            self.items.append(x)
    """)
    assert lint(src, path=OBS) == []


def test_lock_discipline_helper_with_unlocked_call_site_fires():
    src = LOCKED_CLASS % textwrap.dedent("""\
    def add(self, x):
            with self._lock:
                self._bump(x)

        def sneak(self, x):
            self._bump(x)

        def _bump(self, x):
            self.items.append(x)
    """)
    assert ids(lint(src, path=OBS)) == ["lock-discipline"]


def test_lock_discipline_subscript_store_fires():
    src = """\
    import threading
    class Table:
        def __init__(self):
            self._lock = threading.Lock()
            self.rows = {}
        def put(self, k, v):
            self.rows[k] = v
    """
    assert ids(lint(src, path=OBS)) == ["lock-discipline"]


# -- checker: span leaks (ISSUE 14) ------------------------------------------

def test_span_leak_finally_end_is_silent():
    src = """\
    from pulsarutils_tpu.obs.trace import begin_span
    def run():
        h = begin_span("dispatch")
        try:
            work()
        finally:
            h.end()
    """
    assert lint(src, path=OBS) == []


def test_span_leak_straight_line_end_is_silent():
    src = """\
    from pulsarutils_tpu.obs.trace import begin_span
    def run():
        h = begin_span("dispatch")
        x = 1
        h.end()
    """
    assert lint(src, path=OBS) == []


def test_span_leak_branch_before_end_fires():
    src = """\
    from pulsarutils_tpu.obs.trace import begin_span
    def run(flag):
        h = begin_span("dispatch")
        if flag:
            return None       # h never ends on this path
        h.end()
    """
    assert ids(lint(src, path=OBS)) == ["span-leak"]


def test_span_leak_no_end_at_all_fires():
    src = """\
    from pulsarutils_tpu.obs.trace import begin_span
    def run():
        h = begin_span("dispatch")
        work(h)
    """
    assert ids(lint(src, path=OBS)) == ["span-leak"]


def test_span_leak_escaping_handle_fires_and_waives():
    # attribute store / argument / discard: the function cannot
    # guarantee the end — findings, waivable at reviewed seams
    src = """\
    from pulsarutils_tpu.obs.trace import begin_span
    def stash(self):
        self.span = begin_span("lease")
    def discard():
        begin_span("oops")
    """
    assert ids(lint(src, path=OBS)) == ["span-leak", "span-leak"]
    waived = """\
    from pulsarutils_tpu.obs.trace import begin_span
    def stash(self):
        # putpu-lint: disable=span-leak — ends at lease resolution
        self.span = begin_span("lease")
    """
    assert lint(waived, path=OBS) == []


def test_span_leak_end_inside_try_body_fires():
    # an end in the try BODY (not finally) is skipped by an exception
    src = """\
    from pulsarutils_tpu.obs.trace import begin_span
    def run():
        h = begin_span("dispatch")
        try:
            work()
            h.end()
        except ValueError:
            pass
    """
    assert ids(lint(src, path=OBS)) == ["span-leak"]


# -- checker 4: metric/span name drift ---------------------------------------

MANIFEST = {"putpu_known_total"}


def test_metric_name_unknown_fires():
    src = 'reg.counter("putpu_bogus_total")\n'
    found = lint(src, path=OBS, manifest_names=MANIFEST)
    assert ids(found) == ["metric-name-unknown"]


def test_metric_name_declared_is_silent():
    src = 'reg.counter("putpu_known_total")\n'
    assert lint(src, path=OBS, manifest_names=MANIFEST) == []


def test_metric_name_dynamic_counter_suffix_resolves():
    src = 'reg.counter("putpu_dispatches_total")\n'
    assert lint(src, path=OBS, manifest_names=set(),
                dynamic_names={"dispatches"}) == []


def test_metric_name_fstring_fires():
    src = 'reg.counter(f"putpu_{name}_total")\n'
    found = lint(src, path=OBS, manifest_names=MANIFEST)
    assert ids(found) == ["metric-name-dynamic"]


def test_metric_name_unemitted_manifest_entry_fires_on_full_scan():
    project = LintProject(manifest_names={"putpu_known_total",
                                          "putpu_stale_total"})
    project.check_source('reg.counter("putpu_known_total")\n', OBS)
    # the unemitted direction only arms on a full-package scan: cover
    # every emitting layer with trivial files
    for layer in ("parallel", "pipeline", "faults", "io"):
        project.check_source("x = 1\n",
                             f"pulsarutils_tpu/{layer}/fixture.py")
    extra = project.finalize()
    assert ids(extra) == ["metric-name-unemitted"]
    assert "putpu_stale_total" in extra[0].message


def test_metric_name_unknown_doc_reference_fires(tmp_path):
    # a putpu_* token in README/docs must resolve against the manifest
    # parsed (not imported) from obs/names.py
    pkg = tmp_path / "pulsarutils_tpu" / "obs"
    pkg.mkdir(parents=True)
    (pkg / "names.py").write_text(
        'METRIC_NAMES = {"putpu_real_total": "meaning"}\n'
        'BUDGET_COUNTERS = frozenset({"dispatches"})\n')
    (tmp_path / "README.md").write_text(
        "putpu_real_total and putpu_dispatches_total resolve; "
        "putpu_ghost_total does not\n")
    project = LintProject(root=str(tmp_path))
    extra = project.finalize()
    assert ids(extra) == ["metric-name-unknown-ref"]
    assert "putpu_ghost_total" in extra[0].message


@pytest.mark.parametrize("call, fires", [
    ('pl.pallas_call(kernel, out_shape=o, name="fdmt_head")', False),
    ("pl.pallas_call(kernel, out_shape=o)", True),
    ('pl.pallas_call(kernel, out_shape=o, name="kernel")', True),
    ("pl.pallas_call(kernel, out_shape=o, name=label)", True),
])
def test_kernel_name_must_be_declared(tmp_path, call, fires):
    # ISSUE 25: trace reductions find a kernel by its name, so every
    # pallas_call carries a literal name= from the manifest's KERNEL_NAMES
    pkg = tmp_path / "pulsarutils_tpu" / "obs"
    pkg.mkdir(parents=True)
    (pkg / "names.py").write_text(
        'METRIC_NAMES = {}\nKERNEL_NAMES = {"fdmt_head": "meaning"}\n')
    project = LintProject(root=str(tmp_path))
    found = project.check_source(call + "\n", OPS)
    assert ids(found) == (["kernel-name-unknown"] if fires else [])


def test_committed_kernel_names_cover_every_pallas_call():
    # the committed tree lints clean (test_committed_tree_* below), so
    # every pallas_call in it is named from this table; none after the
    # closure that builds it
    assert names.KERNEL_NAMES
    assert not {"kernel", "run", "fn"} & set(names.KERNEL_NAMES)


def test_runtime_manifest_helpers_agree():
    assert names.is_known("putpu_hits_total")
    assert names.is_known(names.budget_counter_metric("dispatches"))
    assert not names.is_known("putpu_ghost_total")


# -- checker 5: broad exception ----------------------------------------------

def test_broad_except_fires_outside_seams():
    src = """\
    def step():
        try:
            work()
        except Exception:
            pass
    """
    assert ids(lint(src, path="pulsarutils_tpu/pipeline/fixture.py")) \
        == ["broad-except"]


def test_bare_except_fires():
    src = "try:\n    work()\nexcept:\n    pass\n"
    assert ids(lint(src, path=OPS)) == ["broad-except"]


def test_narrow_except_is_silent():
    src = """\
    def step():
        try:
            work()
        except (OSError, ValueError):
            pass
    """
    assert lint(src, path="pulsarutils_tpu/pipeline/fixture.py") == []


def test_broad_except_silent_in_containment_seam():
    # obs/server.py _Handler.do_GET is a reviewed seam: a scrape must
    # never take down the survey
    src = """\
    class _Handler:
        def do_GET(self):
            try:
                self.respond()
            except Exception:
                pass
    """
    assert lint(src, path="pulsarutils_tpu/obs/server.py") == []


# -- checker 6: float64 leak -------------------------------------------------

def test_float64_leak_jnp_dtype_fires():
    src = "import jax.numpy as jnp\nx = jnp.zeros((4,), dtype=jnp.float64)\n"
    assert "float64-leak" in ids(lint(src))


def test_float64_leak_string_dtype_fires():
    src = 'import jax.numpy as jnp\nx = jnp.asarray(y, "float64")\n'
    assert ids(lint(src)) == ["float64-leak"]


def test_float64_leak_astype_on_jnp_chain_fires():
    src = 'import jax.numpy as jnp\nx = jnp.abs(y).astype("float64")\n'
    assert ids(lint(src)) == ["float64-leak"]


def test_float64_leak_x64_flag_flip_fires():
    src = 'import jax\njax.config.update("jax_enable_x64", True)\n'
    assert ids(lint(src, path=PAR)) == ["float64-leak"]


def test_float64_host_numpy_is_silent():
    # host-side float64 (offset planning, reference paths) is deliberate
    src = "import numpy as np\nx = np.zeros((4,), dtype=np.float64)\n"
    assert lint(src) == []


def test_float64_leak_silent_outside_device_layers():
    src = "import jax.numpy as jnp\nx = jnp.asarray(y, 'float64')\n"
    assert lint(src, path=OBS) == []


# -- checker: bf16 casts outside the precision seam ---------------------------

def test_bf16_cast_astype_fires_in_ops():
    src = "import jax.numpy as jnp\ny = x.astype(jnp.bfloat16)\n"
    assert ids(lint(src, path=OPS)) == ["bf16-cast"]


def test_bf16_cast_string_dtype_fires_in_parallel():
    src = 'import jax.numpy as jnp\ny = jnp.asarray(x, "bfloat16")\n'
    assert ids(lint(src, path=PAR)) == ["bf16-cast"]


def test_bf16_cast_ctor_kwarg_fires():
    src = ("import jax.numpy as jnp\n"
           "y = jnp.zeros((4,), dtype=jnp.float16)\n")
    assert ids(lint(src, path=OPS)) == ["bf16-cast"]


def test_bf16_cast_convert_element_type_fires():
    src = ("import jax\n"
           "y = jax.lax.convert_element_type(x, jax.numpy.bfloat16)\n")
    assert ids(lint(src, path=OPS)) == ["bf16-cast"]


def test_bf16_dtype_comparison_is_silent():
    # a dtype *guard* is not a cast
    src = ("import jax.numpy as jnp\n"
           "flag = x.dtype == jnp.bfloat16\n")
    assert lint(src, path=OPS) == []


def test_bf16_cast_silent_outside_device_layers():
    # the precision/ seam (and every non-device layer) may spell bf16
    src = "import jax.numpy as jnp\ny = x.astype(jnp.bfloat16)\n"
    assert lint(src, path="pulsarutils_tpu/precision/policy.py") == []
    assert lint(src, path=OBS) == []


def test_bf16_cast_waivable_for_policy_gated_kernel():
    src = ("import jax.numpy as jnp\n"
           "y = x.astype(jnp.bfloat16)"
           "  # putpu-lint: disable=bf16-cast — policy-gated\n")
    assert lint(src, path=OPS) == []


# -- waivers ------------------------------------------------------------------

BROAD = "try:\n    work()\nexcept Exception:\n    pass\n"


def test_waiver_same_line_suppresses():
    src = BROAD.replace(
        "except Exception:",
        "except Exception:  # putpu-lint: disable=broad-except — seam")
    assert lint(src, path=OPS) == []


def test_waiver_line_above_suppresses():
    src = ("try:\n    work()\n"
           "# putpu-lint: disable=broad-except — reviewed\n"
           "except Exception:\n    pass\n")
    assert lint(src, path=OPS) == []


def test_waiver_file_wide_suppresses():
    src = "# putpu-lint: disable-file=broad-except\n" + BROAD * 2
    assert lint(src, path=OPS) == []


def test_waiver_does_not_cross_findings():
    src = BROAD.replace(
        "except Exception:",
        "except Exception:  # putpu-lint: disable=device-trip")
    assert "broad-except" in ids(lint(src, path=OPS))


def test_waiver_in_string_literal_is_inert():
    src = 's = "# putpu-lint: disable=broad-except"\n' + BROAD
    assert "broad-except" in ids(lint(src, path=OPS))


def test_waiver_unknown_id_is_itself_a_finding():
    src = "x = 1  # putpu-lint: disable=not-a-checker\n"
    assert ids(lint(src, path=OPS)) == ["lint-waiver-unknown"]


def test_waiver_parser_multiple_ids():
    w = waivers_mod.parse_waivers(
        "x = 1  # putpu-lint: disable=broad-except,device-trip\n")
    assert w.waives("broad-except", 1)
    assert w.waives("device-trip", 1)
    assert not w.waives("float64-leak", 1)


# -- baseline -----------------------------------------------------------------

BAD_PIPE = "pulsarutils_tpu/pipeline/legacy.py"


def _project_with_finding(src=BROAD):
    project = LintProject()
    project.check_source(src, BAD_PIPE)
    return project


def test_baseline_roundtrip_suppresses(tmp_path):
    path = str(tmp_path / "baseline.json")
    first = _project_with_finding()
    assert save_baseline(path, first.findings, first.sources) == 1
    assert len(load_baseline(path)) == 1

    again = _project_with_finding()
    assert again.apply_baseline(path) == 1
    assert again.new_findings() == []
    assert again.report()["clean"]
    assert again.report()["baselined"] == 1


def test_baseline_survives_line_shift(tmp_path):
    # fingerprints hash content, not line numbers: edits above the
    # grandfathered site must not resurrect it
    path = str(tmp_path / "baseline.json")
    first = _project_with_finding()
    save_baseline(path, first.findings, first.sources)

    shifted = _project_with_finding("# a new comment line\n" + BROAD)
    assert shifted.apply_baseline(path) == 1
    assert shifted.new_findings() == []


def test_baseline_edited_line_resurfaces(tmp_path):
    path = str(tmp_path / "baseline.json")
    first = _project_with_finding()
    save_baseline(path, first.findings, first.sources)

    edited = _project_with_finding(
        BROAD.replace("except Exception:", "except  Exception :"))
    assert edited.apply_baseline(path) == 0
    assert len(edited.new_findings()) == 1


def test_baseline_never_records_waived(tmp_path):
    path = str(tmp_path / "baseline.json")
    src = BROAD.replace(
        "except Exception:",
        "except Exception:  # putpu-lint: disable=broad-except — ok")
    project = _project_with_finding(src)
    assert save_baseline(path, project.findings, project.sources) == 0


def test_baseline_second_identical_violation_is_new(tmp_path):
    # the ordinal in the fingerprint: grandfathering one site must not
    # cover a copy-pasted second one
    path = str(tmp_path / "baseline.json")
    first = _project_with_finding()
    save_baseline(path, first.findings, first.sources)

    doubled = _project_with_finding(BROAD + BROAD)
    assert doubled.apply_baseline(path) == 1
    assert len(doubled.new_findings()) == 1


def test_fingerprint_helper_matches_batch():
    project = _project_with_finding()
    f = project.findings[0]
    fp = baseline_mod.fingerprint(f, project.sources[BAD_PIPE])
    batch = baseline_mod.fingerprints([f], project.sources)
    assert fp == batch[id(f)]


# -- checker: atomic-write (ISSUE 15) ----------------------------------------

def test_atomic_write_constant_json_path_fires():
    src = """\
    import json
    def persist(doc):
        with open("state/progress.json", "w") as f:
            json.dump(doc, f)
    """
    assert ids(lint(src, path="pulsarutils_tpu/io/fixture.py")) \
        == ["atomic-write"]


def test_atomic_write_fstring_and_concat_suffixes_fire():
    src = """\
    def persist(fp, doc, path):
        with open(f"progress_{fp}.json", "w") as f:
            f.write(doc)
        with open(path + ".jsonl", "a") as f:
            f.write(doc)
    """
    assert ids(lint(src, path="pulsarutils_tpu/fleet/fixture.py")) \
        == ["atomic-write", "atomic-write"]


def test_atomic_write_join_tail_fires():
    src = """\
    import os
    def persist(outdir, doc):
        with open(os.path.join(outdir, "fleet_journal.jsonl"),
                  "a") as f:
            f.write(doc)
    """
    assert ids(lint(src, path="pulsarutils_tpu/fleet/fixture.py")) \
        == ["atomic-write"]


def test_atomic_write_reads_and_tmp_and_variables_are_silent():
    # reads, the helper's own .tmp half of the pattern, and
    # operator-named variable paths (--out artifacts) are all fine
    src = """\
    import json
    def load(path, out, doc):
        with open("state/progress.json") as f:
            data = json.load(f)
        with open("state/progress.json", "r") as f:
            data = json.load(f)
        with open(path + ".tmp", "w") as f:
            json.dump(doc, f)
        with open(out, "w") as f:
            json.dump(doc, f)
        return data
    """
    assert lint(src, path="pulsarutils_tpu/io/fixture.py") == []


def test_atomic_write_sanctioned_in_helper_module():
    src = """\
    def append_jsonl(path, line):
        with open("x.jsonl", "a") as f:
            f.write(line)
    """
    assert lint(src, path="pulsarutils_tpu/io/atomic.py") == []


def test_atomic_write_waivable():
    src = """\
    def forge(doc):
        # putpu-lint: disable=atomic-write — test fixture forges a torn file
        with open("torn.json", "w") as f:
            f.write(doc)
    """
    findings = lint_source(textwrap.dedent(src),
                           path="pulsarutils_tpu/io/fixture.py")
    # lint() strips waived findings; prove the waiver (not silence)
    assert findings == []


# -- the CLI + the committed-tree meta-invariant -----------------------------

def _run_cli(*args, check=False):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "putpu_lint.py"),
         *args],
        cwd=REPO, env=env, capture_output=True, text=True, check=check)


def test_committed_tree_is_clean():
    """THE acceptance invariant: zero unwaived findings on the tree."""
    res = _run_cli(os.path.join(REPO, "pulsarutils_tpu"))
    assert res.returncode == 0, res.stdout + res.stderr
    assert "0 new finding(s)" in res.stdout


def test_committed_tree_runs_at_least_six_checkers():
    project = run_lint(root=REPO)
    rep = project.report()
    assert rep["clean"]
    assert {"retrace", "device-trip", "lock-discipline", "metric-name",
            "broad-except", "float64-leak", "bf16-cast", "atomic-write"} \
        <= set(rep["checkers"])
    assert rep["files"] > 50


def test_cli_exits_one_on_new_finding(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(BROAD)
    res = _run_cli(str(bad))
    assert res.returncode == 1
    assert "broad-except" in res.stdout


def test_cli_json_report(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(BROAD)
    out = tmp_path / "report.json"
    res = _run_cli("--format", "json", "--out", str(out), str(bad))
    assert res.returncode == 1
    doc = json.loads(out.read_text())
    assert doc["tool"] == "putpu-lint"
    assert doc["schema_version"] == 1
    assert not doc["clean"]
    assert doc["new"] == 1
    assert doc == json.loads(res.stdout)


def test_cli_list_checkers():
    res = _run_cli("--list-checkers")
    assert res.returncode == 0
    for cid in ("retrace", "device-trip", "lock-discipline",
                "metric-name", "broad-except", "float64-leak",
                "bf16-cast"):
        assert cid in res.stdout


def test_cli_select_narrows_the_run(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(BROAD)
    res = _run_cli("--select", "device-trip", str(bad))
    assert res.returncode == 0  # broad-except not selected


# -- the budget counters' vocabulary -----------------------------------------

def test_undeclared_budget_counter_names_are_flagged():
    counters = {"dispatches": 3, "not_declared": 1}
    assert names.unknown_budget_counters(counters) == ["not_declared"]
    counters.pop("not_declared")
    assert names.unknown_budget_counters(counters) == []


# -- review-hardening regressions (PR 6 code review) --------------------------

def test_waiver_after_statement_does_not_suppress():
    # a comment BELOW a statement is the line-above waiver of the NEXT
    # statement, never a waiver of the one before it
    src = ('x = reg.counter("putpu_bogus_total")\n'
           "# putpu-lint: disable=metric-name-unknown — next line only\n"
           'y = reg.counter("putpu_bogus2_total")\n')
    found = lint(src, path=OBS, manifest_names=MANIFEST)
    assert [f.line for f in found] == [1]  # line 3 waived, line 1 NOT


def test_jit_in_loop_nested_loops_single_finding():
    src = """\
    import jax
    def f(chunks, g):
        for a in chunks:
            for b in a:
                h = jax.jit(g)
    """
    found = [f for f in lint(src, path=OPS)
             if f.checker == "retrace-jit-in-loop"]
    assert len(found) == 1


def test_cli_root_follows_scanned_paths(tmp_path):
    # linting a foreign tree must read/write THAT tree's baseline, not
    # the one in this package's checkout
    pkg = tmp_path / "pulsarutils_tpu"
    pkg.mkdir()
    (pkg / "bad.py").write_text(BROAD)
    repo_baseline = os.path.join(REPO, ".putpu-lint-baseline.json")
    before = open(repo_baseline).read()
    res = _run_cli("--update-baseline", str(pkg))
    assert res.returncode == 0, res.stdout + res.stderr
    assert (tmp_path / ".putpu-lint-baseline.json").exists()
    assert open(repo_baseline).read() == before
    # and the freshly written baseline suppresses on the next run
    res = _run_cli(str(pkg))
    assert res.returncode == 0, res.stdout + res.stderr


def test_update_baseline_partial_path_preserves_unscanned(tmp_path):
    pkg = tmp_path / "pulsarutils_tpu"
    sub = pkg / "sub"
    sub.mkdir(parents=True)
    (pkg / "a.py").write_text(BROAD)
    (sub / "b.py").write_text(BROAD)
    assert _run_cli("--update-baseline", str(pkg)).returncode == 0
    assert _run_cli("--update-baseline", str(sub)).returncode == 0
    doc = json.loads((tmp_path / ".putpu-lint-baseline.json").read_text())
    locs = sorted(e["location"] for e in doc["findings"])
    assert locs == ["pulsarutils_tpu/a.py:3",
                    "pulsarutils_tpu/sub/b.py:3"]


def test_update_baseline_refuses_select(tmp_path):
    pkg = tmp_path / "pulsarutils_tpu"
    pkg.mkdir()
    (pkg / "bad.py").write_text(BROAD)
    res = _run_cli("--update-baseline", "--select", "broad-except",
                   str(pkg))
    assert res.returncode == 2
    assert "unselected" in res.stderr


# -- checker: quarantine-reason vocabulary (ISSUE 19) -------------------------

REASONS_FIXTURE = '''\
FEED_GAP = "feed_gap"
SHED_OVERRUN = "shed_overrun"
QUARANTINE_REASONS = {
    "feed_gap": "unrecoverable feed loss",
    "shed_overrun": "drop-oldest load shedding",
}
'''

REASON_DOC_FIXTURE = '''\
# robustness

<!-- quarantine-reasons:begin -->
| `feed_gap` | quarantine | audit row |
| `shed_overrun` | journal | audit row |
<!-- quarantine-reasons:end -->
'''


def _reason_root(tmp_path, doc=REASON_DOC_FIXTURE):
    faults = tmp_path / "pulsarutils_tpu" / "faults"
    faults.mkdir(parents=True)
    (faults / "reasons.py").write_text(REASONS_FIXTURE)
    (tmp_path / "docs").mkdir()
    (tmp_path / "docs" / "robustness.md").write_text(doc)
    return str(tmp_path)


def test_reason_unknown_literal_fires(tmp_path):
    project = LintProject(root=_reason_root(tmp_path))
    project.check_source(
        'def f(m):\n    m.record(0, 8, "mystery", {})\n',
        "pulsarutils_tpu/faults/fixture.py")
    assert ids(project.findings) == ["quarantine-reason-unknown"]


def test_reason_vocabulary_literal_and_constant_are_silent(tmp_path):
    project = LintProject(root=_reason_root(tmp_path))
    project.check_source(
        "from . import reasons\n"
        "def f(m):\n"
        '    m.record(0, 8, "feed_gap", {})\n'
        "    m.record(0, 8, reasons.SHED_OVERRUN, {})\n",
        "pulsarutils_tpu/faults/fixture.py")
    assert project.findings == []
    assert project.finalize() == []  # documented + not a full scan


def test_reason_dynamic_fires_integrity_composite_sanctioned(tmp_path):
    project = LintProject(root=_reason_root(tmp_path))
    project.check_source(
        "def f(m, x):\n"
        '    m.record(0, 8, f"weird-{x}", {})\n'
        '    m.record(0, 8, "integrity:" + x, {})\n',
        "pulsarutils_tpu/faults/fixture.py")
    assert ids(project.findings) == ["quarantine-reason-dynamic"]


def test_reason_undocumented_vocab_member_fires(tmp_path):
    doc = REASON_DOC_FIXTURE.replace(
        "| `shed_overrun` | journal | audit row |\n", "")
    project = LintProject(root=_reason_root(tmp_path, doc=doc))
    project.check_source("x = 1\n", "pulsarutils_tpu/faults/fixture.py")
    extra = project.finalize()
    assert ids(extra) == ["quarantine-reason-undocumented"]
    assert "shed_overrun" in extra[0].message


def test_reason_doc_row_unknown_to_vocab_fires(tmp_path):
    doc = REASON_DOC_FIXTURE.replace(
        "<!-- quarantine-reasons:end -->",
        "| `ghost_reason` | ? | ? |\n<!-- quarantine-reasons:end -->")
    project = LintProject(root=_reason_root(tmp_path, doc=doc))
    project.check_source("x = 1\n", "pulsarutils_tpu/faults/fixture.py")
    extra = project.finalize()
    assert ids(extra) == ["quarantine-reason-doc-unknown"]
    assert "ghost_reason" in extra[0].message


def test_reason_unused_arms_only_on_full_layer_scan(tmp_path):
    root = _reason_root(tmp_path)
    project = LintProject(root=root)
    project.check_source(
        'def f(m):\n    m.record(0, 8, "feed_gap", {})\n',
        "pulsarutils_tpu/faults/fixture.py")
    for layer in ("obs", "parallel", "pipeline", "io", "ingest"):
        project.check_source("x = 1\n",
                             f"pulsarutils_tpu/{layer}/fixture.py")
    extra = project.finalize()
    assert ids(extra) == ["quarantine-reason-unused"]
    assert "shed_overrun" in extra[0].message
    # the same sources WITHOUT the ingest layer: the sweep is partial,
    # so the dead-vocabulary direction must stay quiet
    partial = LintProject(root=root)
    partial.check_source(
        'def f(m):\n    m.record(0, 8, "feed_gap", {})\n',
        "pulsarutils_tpu/faults/fixture.py")
    for layer in ("obs", "parallel", "pipeline", "io"):
        partial.check_source("x = 1\n",
                             f"pulsarutils_tpu/{layer}/fixture.py")
    assert partial.finalize() == []
