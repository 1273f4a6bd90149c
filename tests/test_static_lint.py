"""repro.analysis.lint: every rule must fire on a minimal trigger, stay
quiet on the nearest non-violation, and honor the inline allowlist — the
three behaviors that make a lint rule trustworthy enough to gate CI.
"""
import textwrap

import pytest

from repro.analysis import RULES, Finding, lint_paths, lint_source, \
    register_rule
from repro.analysis import lint as lint_mod


def run(src, rules=None):
    return lint_source(textwrap.dedent(src), rules=rules)


def rules_hit(src, rules=None):
    return [f.rule for f in run(src, rules)]


# ---------------------------------------------------------------------------
# wall-clock
# ---------------------------------------------------------------------------

class TestWallClock:
    def test_triggers_on_each_clock_fn(self):
        for fn in ("time.time", "time.perf_counter", "time.monotonic",
                   "time.time_ns"):
            src = f"import time\nt = {fn}()\n"
            assert rules_hit(src) == ["wall-clock"], fn

    def test_triggers_through_import_alias(self):
        assert rules_hit("import time as t\nx = t.monotonic()\n") \
            == ["wall-clock"]

    def test_injected_clock_is_clean(self):
        # the fix the rule demands: reads go through an injected object
        src = """
        def run(clock):
            return clock.monotonic()
        """
        assert rules_hit(src) == []

    def test_time_sleep_is_not_a_clock_read(self):
        assert rules_hit("import time\ntime.sleep(0.1)\n") == []

    def test_local_time_object_is_not_the_module(self):
        # 'time' that was never imported is a local, not stdlib time
        assert rules_hit("def f(time):\n    return time.time()\n") == []

    def test_allowlist_same_line(self):
        src = ("import time\n"
               "t = time.monotonic()  # lint: allow-wall-clock\n")
        assert rules_hit(src) == []

    def test_allowlist_comment_line_above(self):
        src = ("import time\n"
               "# lint: allow-wall-clock — measuring real compile time\n"
               "t = time.monotonic()\n")
        assert rules_hit(src) == []

    def test_allowlist_is_per_rule(self):
        # allowing a DIFFERENT rule does not silence this one
        src = ("import time\n"
               "t = time.time()  # lint: allow-bare-except\n")
        assert rules_hit(src) == ["wall-clock"]


# ---------------------------------------------------------------------------
# unseeded-random
# ---------------------------------------------------------------------------

class TestUnseededRandom:
    def test_stdlib_module_global_triggers(self):
        assert rules_hit("import random\nx = random.uniform(0, 1)\n") \
            == ["unseeded-random"]

    def test_legacy_numpy_global_triggers(self):
        src = "import numpy as np\nx = np.random.uniform(0, 1)\n"
        assert rules_hit(src) == ["unseeded-random"]

    def test_default_rng_is_clean(self):
        src = ("import numpy as np\n"
               "rng = np.random.default_rng(0)\n"
               "x = rng.uniform(0, 1)\n")
        assert rules_hit(src) == []

    def test_seedable_instance_is_clean(self):
        assert rules_hit("import random\nr = random.Random(0)\n") == []

    def test_jax_prng_is_clean(self):
        src = ("import jax\n"
               "k = jax.random.PRNGKey(0)\n"
               "x = jax.random.normal(k, (4,))\n")
        assert rules_hit(src) == []

    def test_allowlist(self):
        src = ("import random\n"
               "x = random.uniform(0, 1)  # lint: allow-unseeded-random\n")
        assert rules_hit(src) == []


# ---------------------------------------------------------------------------
# host-sync (reachability from jitted entry points)
# ---------------------------------------------------------------------------

_JITTED_SYNC = """
import jax
import numpy as np

def helper(x):
    return np.asarray(x)

def forward(x):
    return helper(x) + 1

jit_forward = jax.jit(forward)
"""


class TestHostSync:
    def test_sync_reachable_from_jit_root_triggers(self):
        fs = run(_JITTED_SYNC)
        assert [f.rule for f in fs] == ["host-sync"]
        # the message must name both the sync call and the function
        assert "numpy.asarray" in fs[0].message
        assert "helper" in fs[0].message

    def test_method_item_triggers(self):
        src = """
        import jax

        @jax.jit
        def forward(x):
            return float(x.item())
        """
        assert rules_hit(src) == ["host-sync"]

    def test_sync_outside_jitted_paths_is_clean(self):
        # same np.asarray, but nothing in the module is jitted from it
        src = """
        import numpy as np

        def load(path):
            return np.asarray(open(path).read().split())
        """
        assert rules_hit(src) == []

    def test_unreachable_sibling_is_clean(self):
        src = """
        import jax
        import numpy as np

        def telemetry(x):
            return np.asarray(x)   # never called from forward

        def forward(x):
            return x + 1

        jit_forward = jax.jit(forward)
        """
        assert rules_hit(src) == []

    def test_self_method_edge_is_followed(self):
        src = """
        import jax
        import numpy as np

        class Model:
            def pull(self, x):
                return np.asarray(x)

            def forward(self, x):
                return self.pull(x)

            def compile(self):
                return jax.jit(self.forward)
        """
        assert rules_hit(src) == ["host-sync"]

    def test_decorator_root(self):
        src = """
        import jax
        import numpy as np

        @jax.jit
        def forward(x):
            return np.asarray(x)
        """
        assert rules_hit(src) == ["host-sync"]

    def test_allowlist(self):
        src = _JITTED_SYNC.replace(
            "return np.asarray(x)",
            "return np.asarray(x)  # lint: allow-host-sync")
        assert rules_hit(src) == []


# ---------------------------------------------------------------------------
# interpret-pinned
# ---------------------------------------------------------------------------

class TestInterpretPinned:
    def test_hardcoded_true_triggers(self):
        src = """
        from jax.experimental import pallas as pl

        def launch(x):
            return pl.pallas_call(x, interpret=True)
        """
        assert rules_hit(src) == ["interpret-pinned"]

    def test_threaded_flag_is_clean(self):
        """The one clean form: the flag derived from the platform at the
        call site (``repro.kernels.platform.interpret_mode``)."""
        src = """
        from jax.experimental import pallas as pl
        from repro.kernels.platform import interpret_mode

        def launch(x):
            return pl.pallas_call(x, interpret=interpret_mode())
        """
        assert rules_hit(src) == []

    @pytest.mark.parametrize("launch", [
        "def launch(x, *, interpret=True):\n"
        "            return pl.pallas_call(x, interpret=interpret)",
        "def launch(x):\n"
        "            return pl.pallas_call(x)",
        "def launch(x):\n"
        "            return pl.pallas_call(x, interpret=False)",
    ], ids=["threaded-parameter", "omitted", "pinned-false"])
    def test_flag_not_derived_triggers(self, launch):
        src = f"""
        from jax.experimental import pallas as pl

        {launch}
        """
        assert rules_hit(src) == ["interpret-pinned"]

    def test_allowlist(self):
        src = """
        from jax.experimental import pallas as pl

        def launch(x):
            # lint: allow-interpret-pinned
            return pl.pallas_call(x, interpret=True)
        """
        assert rules_hit(src) == []


# ---------------------------------------------------------------------------
# bare-except + mutable-pytree
# ---------------------------------------------------------------------------

class TestHygieneRules:
    def test_bare_except_triggers(self):
        src = "try:\n    x = 1\nexcept:\n    pass\n"
        assert rules_hit(src) == ["bare-except"]

    def test_named_except_is_clean(self):
        src = "try:\n    x = 1\nexcept (OSError, ValueError):\n    pass\n"
        assert rules_hit(src) == []

    def test_mutable_pytree_triggers(self):
        src = """
        import dataclasses
        import jax

        @jax.tree_util.register_pytree_node_class
        @dataclasses.dataclass
        class Plan:
            x: int
        """
        assert rules_hit(src) == ["mutable-pytree"]

    def test_registration_by_call_form_triggers(self):
        src = """
        import dataclasses
        from jax.tree_util import register_pytree_node_class

        @dataclasses.dataclass
        class Plan:
            x: int

        register_pytree_node_class(Plan)
        """
        assert rules_hit(src) == ["mutable-pytree"]

    def test_frozen_pytree_is_clean(self):
        src = """
        import dataclasses
        import jax

        @jax.tree_util.register_pytree_node_class
        @dataclasses.dataclass(frozen=True)
        class Plan:
            x: int
        """
        assert rules_hit(src) == []

    def test_unregistered_mutable_dataclass_is_clean(self):
        src = """
        import dataclasses

        @dataclasses.dataclass
        class Config:
            x: int
        """
        assert rules_hit(src) == []


# ---------------------------------------------------------------------------
# registry + drivers
# ---------------------------------------------------------------------------

class TestRegistryAndDrivers:
    def test_every_rule_documents_its_history(self):
        for name, rule in RULES.items():
            assert rule.history, f"rule {name!r} has no history note"

    def test_unknown_rule_rejected_with_listing(self):
        with pytest.raises(ValueError, match="wall-clock"):
            lint_source("x = 1\n", rules=["no-such-rule"])

    def test_rule_selection_restricts(self):
        src = ("import time\nimport random\n"
               "t = time.time()\nx = random.random()\n")
        assert rules_hit(src, rules=["wall-clock"]) == ["wall-clock"]

    def test_register_rule_latest_wins(self):
        saved = dict(RULES)
        try:
            @register_rule("wall-clock", history="override")
            def silent(mod):
                return []
            assert rules_hit("import time\nt = time.time()\n") == []
        finally:
            RULES.clear()
            RULES.update(saved)

    def test_finding_key_excludes_line_number(self):
        a = Finding("r", "p.py", 10, 1, "m", snippet="x = time.time()")
        b = Finding("r", "p.py", 99, 1, "m", snippet="x = time.time()")
        assert a.key == b.key

    def test_lint_paths_recurses_and_reports_relative(self, tmp_path):
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "bad.py").write_text("import time\nt = time.time()\n")
        (pkg / "ok.py").write_text("x = 1\n")
        fs = lint_paths([tmp_path], root=tmp_path)
        assert [(f.path, f.rule) for f in fs] == [("pkg/bad.py",
                                                   "wall-clock")]

    def test_syntax_error_becomes_parse_error_finding(self, tmp_path):
        (tmp_path / "broken.py").write_text("def f(:\n")
        fs = lint_paths([tmp_path], root=tmp_path)
        assert [f.rule for f in fs] == ["parse-error"]

    def test_repo_src_is_lint_clean_modulo_baseline(self):
        """The committed tree must produce EXACTLY the grandfathered
        baseline — the live twin of `check_static.py --strict` in CI."""
        import json
        import pathlib
        root = pathlib.Path(lint_mod.__file__).resolve().parents[3]
        fs = lint_paths([root / "src"], root=root)
        with open(root / "tools" / "static_baseline.json") as fh:
            baseline = json.load(fh)["lint"]
        from collections import Counter
        counts = Counter(f.key for f in fs)
        grown = {k: c for k, c in counts.items() if c > baseline.get(k, 0)}
        assert not grown, f"new lint findings not in baseline: {grown}"
