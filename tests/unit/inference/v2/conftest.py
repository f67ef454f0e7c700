"""The fixtures of the served model kinds' files, built once from the
``CASE`` a ``test_<kind>.py`` states (``kinds.Case``), module-scoped; and the
tables of a case turned into the parameters of the tests that name them."""

import pytest

import jax
import jax.numpy as jnp

pytest.register_assert_rewrite("unit.inference.v2.kind_conformance", "unit.inference.v2.kinds")

from unit.inference.v2 import kinds  # noqa: E402

from deepspeed_tpu.inference.v2 import InferenceEngineV2  # noqa: E402
from deepspeed_tpu.models import build_model  # noqa: E402


@pytest.fixture(scope="module")
def case(request):
    return request.module.CASE


@pytest.fixture(scope="module")
def model(case):
    return build_model(case.preset, **case.preset_over)


def _engine(case, model):
    return InferenceEngineV2(model=model, config=kinds.engine_config(case), dtype=jnp.float32,
                             rng=jax.random.PRNGKey(case.rng))


@pytest.fixture(scope="module")
def engine(case, model):
    return _engine(case, model)


@pytest.fixture(scope="module")
def kernel_engine(case, model):
    """An engine whose programs are first run under ``DS_PALLAS=1``
    (``state_step``'s second case), so that they hold the state step's
    kernel, interpreted."""
    return _engine(case, model)


@pytest.fixture
def state_step(request, monkeypatch):
    """What serves the kind's state step in the test, as a step record names
    it: ``xla`` (the fallback), or the kernel - ``DS_PALLAS=1`` forces the
    kernel paths, interpreted off the chip. None for a kind without one."""
    if request.param not in (None, "xla"):
        monkeypatch.setenv("DS_PALLAS", "1")
    return request.param


@pytest.fixture
def step_engine(request, state_step):
    """The module's engine whose programs were traced under ``state_step``."""
    return request.getfixturevalue("engine" if state_step in (None, "xla") else "kernel_engine")


@pytest.fixture(scope="module")
def tokens(case):
    return case.tokens()


@pytest.fixture(scope="module")
def reference(case, engine, tokens):
    """``kinds.jitted_reference`` on the module's engine's weights (taken
    now: a gateway's shutdown takes an engine's)."""
    return kinds.jitted_reference(case, engine.params, engine.model_config, len(tokens[0]))


def _chunks(row):
    return "-".join(str(n) for n in (row[0], row[1], *row[2]))


# an argument of a test -> the table of the case that gives its values, and their ids
TABLES = {"refused": ("refused", lambda row: row.field),
          "subsystem": ("subsystems", lambda row: row[0]),
          "prefill": ("prefill", _chunks), "cut": ("cuts", _chunks), "plan": ("plans", str)}


def pytest_generate_tests(metafunc):
    case = getattr(metafunc.module, "CASE", None)
    if case is None:
        return
    for name, (table, ident) in TABLES.items():
        if name in metafunc.fixturenames:
            metafunc.parametrize(name, list(getattr(case, table)), ids=ident)
    if "state_step" in metafunc.fixturenames:
        # a kind's own tests of its state step run both ways; the shared ones it lists
        own = metafunc.function.__module__ == metafunc.module.__name__
        kernel = own or metafunc.function.__name__ in case.kernel_tests
        # a name that no test of the class bears would drop its kernel case without a word
        assert own or all(hasattr(metafunc.cls, name) for name in case.kernel_tests), \
            case.kernel_tests
        steps = ["xla", case.state_step] if case.state_step and kernel else \
            ["xla" if case.state_step else None]
        metafunc.parametrize("state_step", steps, ids=[s or "xla" for s in steps], indirect=True)
