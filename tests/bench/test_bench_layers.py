"""The model's scopes reach the compiled LM tick where ``bench/scopes.py``'s
layers look for them, and it reads them: plain or wrapped in their
transforms, a loop with its body once, whole names only.

The tick is the cell's own (``bench/drivers/lm.py``) at the tests' one-layer
size, compiled on the CPU: which scopes reach which ``op_name`` is decided
when JAX lowers the program, not by the backend."""
import pytest

from bench import run as R
from bench import scopes as S
from bench import trace as tr
from bench.peaks import peaks_for
from small_sizes import LM_CELL, lm_small

LAYERS = tuple(S.LAYERS)
SCOPES = [s for _, scopes in S.LAYERS.values() for s in scopes]
MODEL_SCOPES = [s for s in SCOPES if s != "train.adamw"]


@pytest.fixture(scope="module")
def tick():
    """(module name, instruction -> op_name path) of the compiled tick."""
    spec, entry, cfg, traffic = R.load_cell(LM_CELL)
    lm_small(cfg, traffic)
    cell = R.driver(cfg)(cfg, traffic, 7, 1)
    cell.setup()
    (fn, args), = cell.programs()
    return tr.scope_map(fn.lower(*args).compile().as_text())


def _paths(tick, *names):
    """The op_name paths of the tick that carry every one of ``names``."""
    return [p for p in tick[1].values()
            if set(names) <= S.path_names(p)]


def read(name, ctx):
    per, scopes = S.LAYERS[name]
    return S.layer_ms(ctx, per, *scopes)


def test_readers_read_nine_distinct_scopes():
    assert len(SCOPES) == len(set(SCOPES)) == 9


@pytest.mark.parametrize("scope", MODEL_SCOPES)
def test_scope_runs_in_the_eval(tick, scope):
    assert _paths(tick, "manage.eval", scope)


@pytest.mark.parametrize("scope", MODEL_SCOPES)
def test_scope_runs_in_the_retrains_backward(tick, scope):
    back = [p for p in _paths(tick, "manage.retrain", scope)
            if "transpose(" in p]
    assert back


def test_adamw_runs_in_the_retrain(tick):
    assert _paths(tick, "manage.retrain", "train.adamw")
    assert not _paths(tick, "manage.eval", "train.adamw")


@pytest.mark.parametrize("scope", [s for s in SCOPES
                                   if s.startswith("ssm.")])
def test_ssm_scope_is_rematerialised(tick, scope):
    assert _paths(tick, "rematted_computation", scope)


def test_each_scoped_op_is_in_one_layer_under_eval_or_retrain(tick):
    # so the layers' per-tick sum is at most eval_ms + retrain_ms * r / t;
    # a reducer's body ("lm.norm/reduce_sum") is no op of its own
    for p in tick[1].values():
        names = S.path_names(p)
        if p.startswith("jit(") and names & set(SCOPES):
            assert len(names & set(SCOPES)) == 1, p
            assert names & {"manage.eval", "manage.retrain"}, p


def test_head_and_loss_reach_the_retrain_wrapped(tick):
    # the trap layer_s exists for: an exact component match misses them
    for scope in ("lm.head", "lm.loss"):
        paths = _paths(tick, "manage.retrain", scope)
        assert any(f"transpose(jvp({scope}))" in p for p in paths)
        assert all(scope not in p.split("/") for p in paths)


def test_path_names_peel_transform_wrappers():
    p = ("jit(step)/manage.retrain/cond/transpose(jvp(lm.head))/dot_general;"
         "while/body")
    assert {"lm.head", "manage.retrain", "step", "dot_general", "while",
            "body"} <= S.path_names(p)
    assert "ssm.ssd" not in S.path_names("jit(f)/ssm.ssd_x/add")


class _Cell:
    cfg = {}

    def __init__(self, ticks=4, retrains=1):
        self.counts = {"ticks": ticks, "retrains": retrains}


def _ctx(module, names, ops, cell=None):
    trace = tr.Trace(ops=[tr.Op(0, module, n, a, d) for n, a, d in ops],
                     spans=[], devices=1)
    return R.Context(trace, {module: names}, 0, 10_000, cell or _Cell(),
                     peaks_for("TPU v5 lite"), 1)


def _one(tick, test):
    return next(i for i, p in tick[1].items() if test(p))


def test_layer_s_counts_plain_and_wrapped_once_and_whole_names(tick):
    module, names = tick
    loop = _one(tick, lambda p: p.endswith("/checkpoint/ssm.ssd/while")
                and "manage.eval" in p)
    body = _one(tick, lambda p: "/checkpoint/ssm.ssd/while/body/" in p
                and "manage.eval" in p)
    head = _one(tick, lambda p: "transpose(jvp(lm.head))" in p)
    loss = _one(tick, lambda p: "/jvp(lm.loss)/" in p)
    names = dict(names, **{"fusion.prefix": names[body].replace(
        "ssm.ssd", "ssm.ssd_x")})
    ctx = _ctx(module, names, [(loop, 0, 100), (body, 10, 20),
                               (head, 200, 50), (loss, 300, 10),
                               ("fusion.prefix", 400, 50)])
    assert S.layer_s(ctx, "ssm.ssd") == pytest.approx(100e-9)
    assert S.layer_s(ctx, "lm.head", "lm.loss") == pytest.approx(60e-9)
    assert S.layer_s(ctx, "ssm.ssd_x") == pytest.approx(50e-9)
    assert ctx.scope_s("lm.head") == 0
    assert S.layer_s(ctx, "no.such_scope") is None


# per scope: an op of that length in ns, laid end to end
LENGTHS = {"ssm.in_proj": 10, "ssm.out_proj": 20, "ssm.ssd": 40,
           "lm.norm": 1, "ssm.conv": 2, "ssm.gate_norm": 4, "lm.head": 100,
           "lm.loss": 200, "train.adamw": 1000}


def _layered_ctx(tick, cell=None):
    module, names = tick
    ops, t = [], 0
    for scope, n in LENGTHS.items():
        ops.append((_one(tick, lambda p: S.path_names(p) & set(SCOPES)
                         == {scope}), t, n))
        t += 2 * n
    return _ctx(module, names, ops, cell)


@pytest.mark.parametrize("name,want", [
    ("proj_ms.lm", 30e-6 / 4), ("ssd_ms.lm", 40e-6 / 4),
    ("pointwise_ms.lm", 7e-6 / 4), ("head_ms.lm", 300e-6 / 4),
    ("adamw_ms.lm", 1000e-6 / 1),
])
def test_layer_reader(tick, name, want):
    assert read(name, _layered_ctx(tick)) == pytest.approx(want)


def test_adamw_reads_nothing_without_a_retrain(tick):
    assert read("adamw_ms.lm", _layered_ctx(tick, _Cell(retrains=0))) is None


@pytest.mark.parametrize("name", LAYERS)
def test_layer_reader_on_a_program_without_the_scopes(name):
    # a tick built without the scopes: the layer reads nothing
    ctx = _ctx("jit_step", {"fusion.1": "jit(step)/manage.eval/dot"},
               [("fusion.1", 0, 10)])
    assert read(name, ctx) is None
