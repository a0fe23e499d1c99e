"""Continuous-deployment tests: publish/watch, in-place reload, canary gate.

The zero-downtime acceptance criteria on CPU with a tiny model:

- the trainer's manifest-commit fence atomically publishes a ``latest``
  pointer, and the CheckpointWatcher NEVER hands an unverified or torn dir
  to its callback (corrupt dirs are rejected with the failing file named);
- ``engine.reload_params`` swaps the full merged tree in place with zero
  steady-state retraces, and an identical tree yields token-identical
  greedy output across the swap;
- the server's ``/admin/reload`` fences the swap between decode rounds:
  in-flight requests finish (on the old weights), the version only moves
  on full success, and an injected apply failure (``deploy_reload``) fails
  closed with the old weights still serving;
- the RollingUpdater's canary gate rolls the WHOLE fleet back on a
  divergent replica while concurrent in-flight requests all complete, and
  a crash mid-update (``deploy_crash_mid_update``) leaves a mixed fleet
  that a plain re-run converges to one consistent version.
"""

import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from relora_tpu.config.model import ModelConfig
from relora_tpu.models.params_util import init_params
from relora_tpu.serve import deploy
from relora_tpu.serve.engine import InferenceEngine, build_decode_model
from relora_tpu.serve.sampling import SamplingParams
from relora_tpu.utils import faults

from tests.test_server import _Server, _generate, _http  # shared serving idioms

pytestmark = pytest.mark.serve

TINY = ModelConfig(
    family="llama",
    vocab_size=256,
    hidden_size=64,
    intermediate_size=160,
    num_hidden_layers=2,
    num_attention_heads=4,
    max_sequence_length=512,
)
CACHE = 512


@pytest.fixture
def disarm_faults():
    faults.reset()
    yield
    faults.reset()


def _build_engine():
    model = build_decode_model(TINY, cache_size=CACHE)
    base = type(model)(TINY, lora=None, dtype=jnp.float32, scan_layers=True)
    params = init_params(base, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    return InferenceEngine(TINY, params, cache_size=CACHE)


@pytest.fixture(scope="module")
def engine():
    return _build_engine()


@pytest.fixture(scope="module")
def engine_b():
    return _build_engine()


def _host_tree(engine):
    return jax.tree_util.tree_map(np.asarray, jax.device_get(engine.params))


def _perturb_tree(tree, seed):
    """A deterministically different model: additive noise on every leaf.
    (Uniform scaling would be normalized away by RMSNorm and leave greedy
    argmax unchanged — noise actually moves the canary outputs.)"""
    rng = np.random.RandomState(seed)
    return jax.tree_util.tree_map(
        lambda x: np.asarray(x) + rng.normal(scale=0.1, size=np.shape(x)).astype(
            np.asarray(x).dtype
        ),
        tree,
    )


def _greedy(engine, prompt, n=8):
    return engine.generate(
        [prompt],
        max_new_tokens=n,
        sampling=SamplingParams(temperature=0.0),
        eos_id=-1,
        key=jax.random.PRNGKey(0),
    )[0]


# -- publish + watch ----------------------------------------------------------


def test_checkpoint_step_parses_model_dirs():
    assert deploy.checkpoint_step("/a/b/model_32") == 32
    assert deploy.checkpoint_step("model_0") == 0
    assert deploy.checkpoint_step("/a/b/model_32/") == 32
    assert deploy.checkpoint_step("/a/b/notacheckpoint") is None
    assert deploy.checkpoint_step("/a/b/model_x") is None


def test_publish_and_read_latest_atomic(tmp_path):
    save_dir = str(tmp_path)
    ckpt = tmp_path / "model_16"
    ckpt.mkdir()
    deploy.publish_latest(save_dir, str(ckpt))
    assert deploy.read_latest(save_dir) == str(ckpt)
    # a torn pointer write must read as absent, not as an error
    with open(tmp_path / deploy.LATEST_FILE, "w") as f:
        f.write('{"path": "mod')
    assert deploy.read_latest(save_dir) is None
    # pointer escaping the save dir is refused
    with open(tmp_path / deploy.LATEST_FILE, "w") as f:
        json.dump({"path": "../evil"}, f)
    assert deploy.read_latest(save_dir) is None


def _save_real_checkpoint(tmp_path, step, devices):
    """A real manifest-committed checkpoint via the trainer's save path."""
    from relora_tpu.parallel.mesh import MeshSpec, make_mesh
    from relora_tpu.train import checkpoint as ckpt
    from tests.test_checkpoint import make_state

    mesh = make_mesh(MeshSpec(data=1, fsdp=8))
    state = make_state(mesh, 8)
    path = ckpt.save_checkpoint(str(tmp_path), step, state, {"update_step": step})
    ckpt.wait_for_save()
    return path


def _corrupt_state_file(path):
    """Flip one byte in a state payload file; returns the file touched."""
    for root, _dirs, files in os.walk(os.path.join(path, "state")):
        for name in files:
            target = os.path.join(root, name)
            if os.path.getsize(target) > 0:
                with open(target, "r+b") as f:
                    byte = f.read(1)
                    f.seek(0)
                    f.write(bytes([byte[0] ^ 0xFF]))
                return target
    raise AssertionError(f"no non-empty state file under {path}")


def test_trainer_publishes_latest_at_manifest_commit(tmp_path, devices):
    path = _save_real_checkpoint(tmp_path, 16, devices)
    assert deploy.read_latest(str(tmp_path)) == os.path.abspath(path)


def test_watcher_never_acts_on_unverified_dirs(tmp_path, devices):
    accepted, rejected = [], []
    watcher = deploy.CheckpointWatcher(
        str(tmp_path),
        accepted.append,
        on_reject=lambda path, reason: rejected.append((path, reason)),
    )
    assert watcher.poll_once() is None  # no pointer yet: nothing to do

    path = _save_real_checkpoint(tmp_path, 16, devices)
    bad_file = _corrupt_state_file(path)
    assert watcher.poll_once() is None
    assert accepted == []  # the gate held
    assert len(rejected) == 1
    assert os.path.basename(bad_file) in rejected[0][1]  # names the file
    # unchanged bad dir: remembered, not re-verified and not re-reported
    assert watcher.poll_once() is None
    assert len(rejected) == 1

    # a new good checkpoint re-publishes the pointer; the watcher fires
    good = _save_real_checkpoint(tmp_path, 24, devices)
    assert watcher.poll_once() == os.path.abspath(good)
    assert accepted == [os.path.abspath(good)]
    # already current: no re-fire
    assert watcher.poll_once() is None
    assert len(accepted) == 1

    # a rollout that reports failure (on_new -> False) is NOT latched: the
    # next poll retries the same verified checkpoint until it succeeds
    newer = _save_real_checkpoint(tmp_path, 32, devices)
    attempts = []
    outcomes = [False, False, True]
    watcher.on_new = lambda p: (attempts.append(p), outcomes[len(attempts) - 1])[1]
    for _ in range(2):
        assert watcher.poll_once() is None  # failed rollout: retried
    assert watcher.poll_once() == os.path.abspath(newer)  # third try sticks
    assert attempts == [os.path.abspath(newer)] * 3
    assert watcher.poll_once() is None  # latched only after success


def test_restore_serving_params_refuses_corrupt_checkpoint(tmp_path, devices):
    from relora_tpu.train.checkpoint import restore_serving_params

    path = _save_real_checkpoint(tmp_path, 16, devices)
    bad_file = _corrupt_state_file(path)
    with pytest.raises(ValueError, match="refusing to serve"):
        restore_serving_params(path)
    try:
        restore_serving_params(path)
    except ValueError as e:
        assert os.path.basename(bad_file) in str(e)  # error names the file


# -- in-place engine reload ---------------------------------------------------


def test_reload_params_token_identical_and_zero_retrace(engine):
    prompt = [1, 2, 3, 4]
    before = _greedy(engine, prompt)
    host = _host_tree(engine)
    retraces0 = engine.compile_watcher.steady_state_retraces
    for _ in range(3):  # repeated reloads must pin ONE compiled signature
        engine.reload_params(host)
    after = _greedy(engine, prompt)
    assert after == before  # same weights in, token-identical greedy out
    assert engine.compile_watcher.steady_state_retraces == retraces0


def test_reload_params_changes_output_and_swaps_back(engine):
    prompt = [5, 6, 7]
    host = _host_tree(engine)
    before = _greedy(engine, prompt)
    engine.reload_params(_perturb_tree(host, seed=7))
    engine.reload_params(host)  # swap back
    assert _greedy(engine, prompt) == before


def _break_first_leaf(tree):
    """Replace the first array leaf with a wrong-shape array, in place."""
    for key, value in tree.items():
        if isinstance(value, dict):
            if _break_first_leaf(value):
                return True
        else:
            tree[key] = np.zeros((3, 3), np.float32)
            return True
    return False


def test_reload_params_rejects_bad_trees(engine):
    import copy

    host = _host_tree(engine)
    bad = copy.deepcopy(host)
    assert _break_first_leaf(bad)
    with pytest.raises(ValueError, match="shape"):
        engine.reload_params(bad)
    with pytest.raises(ValueError, match="does not exist in the live tree"):
        engine.reload_params({**host, "not_a_real_leaf": np.zeros(3, np.float32)})


# -- server reload boundary ---------------------------------------------------


def _serving_fleet_server(engine, trees, *, version=1, checkpoint="/ckpt/model_1", **kw):
    """A _Server whose /admin/reload maps fake checkpoint paths to prepared
    host trees — the transport/fencing layer under test, no disk IO."""

    def reload_prepare(path):
        tree = trees.get(os.path.abspath(path))
        if tree is None:
            raise ValueError(f"refusing to serve corrupt checkpoint {path}")
        return lambda: engine.reload_params(tree)

    return _Server(
        engine,
        reload_prepare=reload_prepare,
        weights_version=version,
        weights_checkpoint=checkpoint,
        **kw,
    )


def test_server_reload_between_decode_rounds(engine, disarm_faults):
    host = _host_tree(engine)
    trees = {"/ckpt/model_1": host, "/ckpt/model_2": host}
    with _serving_fleet_server(engine, trees, max_batch=2, max_queue=32) as server:
        port = server.port
        status, headers, _ = _http(port, "GET", "/healthz")
        payload = json.loads(_http(port, "GET", "/healthz")[2])
        assert payload["weights_version"] == 1
        assert payload["weights_checkpoint"] == "/ckpt/model_1"
        # the bytes of the weights as held: on /metrics before any round runs
        assert server.stats.gauge_value("param_bytes") == engine.param_bytes() > 0
        assert "param_bytes" in _http(port, "GET", "/metrics")[2].decode()
        server.stats.set_gauge("param_bytes", 0)

        # concurrent load across the swap: nothing may drop
        results = []

        def pound():
            for _ in range(4):
                tokens, final = _generate(
                    port, {"prompt": [1, 2, 3], "max_new_tokens": 6}
                )
                results.append(final["finish_reason"])

        threads = [threading.Thread(target=pound) for _ in range(2)]
        for t in threads:
            t.start()
        status, _headers, body = _http(
            port, "POST", "/admin/reload", {"checkpoint": "/ckpt/model_2"}
        )
        for t in threads:
            t.join(120)
        assert status == 200, body
        reply = json.loads(body)
        assert reply["ok"] is True and reply["weights_version"] == 2
        assert server.stats.gauge_value("param_bytes") == engine.param_bytes()  # set again by the reload alone
        assert len(results) == 8
        assert all(r in ("length", "eos") for r in results)  # zero dropped

        # the new version is on healthz AND stamped on every response
        assert json.loads(_http(port, "GET", "/healthz")[2])["weights_version"] == 2
        _status, headers, _body = _http(
            port, "POST", "/v1/generate", {"prompt": [1], "max_new_tokens": 2}
        )
        assert headers.get("x-relora-weights") == "2"

        # unknown checkpoint: prepare fails -> 422, version does not move
        status, _h, body = _http(
            port, "POST", "/admin/reload", {"checkpoint": "/ckpt/nope"}
        )
        assert status == 422
        assert json.loads(_http(port, "GET", "/healthz")[2])["weights_version"] == 2


@pytest.mark.faults
def test_injected_reload_failure_fails_closed(engine, disarm_faults):
    host = _host_tree(engine)
    trees = {"/ckpt/model_1": host, "/ckpt/model_2": host}
    faults.configure("deploy_reload", exc=RuntimeError)
    with _serving_fleet_server(engine, trees, max_queue=8) as server:
        port = server.port
        status, _h, body = _http(
            port, "POST", "/admin/reload", {"checkpoint": "/ckpt/model_2"}
        )
        assert status == 500
        reply = json.loads(body)
        assert reply["ok"] is False and "injected fault" in reply["error"]
        # failed closed: old version, old weights, still serving
        payload = json.loads(_http(port, "GET", "/healthz")[2])
        assert payload["status"] == "ok" and payload["weights_version"] == 1
        tokens, final = _generate(port, {"prompt": [1, 2], "max_new_tokens": 4})
        assert final["finish_reason"] in ("length", "eos")
        # the fault fired once; the retry goes through
        status, _h, body = _http(
            port, "POST", "/admin/reload", {"checkpoint": "/ckpt/model_2"}
        )
        assert status == 200 and json.loads(body)["weights_version"] == 2


# -- rolling update + canary + rollback ---------------------------------------


def _fleet(engine, engine_b, trees_a, trees_b):
    a = _serving_fleet_server(engine, trees_a, max_batch=2, max_queue=32)
    b = _serving_fleet_server(engine_b, trees_b, max_batch=2, max_queue=32)
    return a, b


def _updater(ports, events):
    return deploy.RollingUpdater(
        lambda: {i: ("127.0.0.1", p) for i, p in enumerate(ports)},
        canary_prompts=[[1, 2, 3], [7, 8]],
        canary_max_new_tokens=4,
        emit=lambda event, idx, detail: events.append((event, idx, detail)),
        probe_timeout_s=30.0,
        verify=lambda path: (True, "ok"),  # fake paths; transport under test
    )


def test_updater_refuses_partial_fleet():
    # a half-booted fleet (replica without a port yet) must not be walked:
    # updating only the visible replicas would latch a mixed-version fleet
    events = []
    updater = deploy.RollingUpdater(
        lambda: {0: ("127.0.0.1", 1), 1: ("127.0.0.1", None)},
        expect_replicas=2,
        emit=lambda event, idx, detail: events.append((event, idx, detail)),
        verify=lambda path: (True, "ok"),
    )
    assert updater.run("/ckpt/model_5") is False
    assert [e[0] for e in events] == ["deploy_reject"]
    assert "1/2" in str(events[0][2])


@pytest.mark.faults
def test_canary_failure_rolls_whole_fleet_back(engine, engine_b, disarm_faults):
    host_a, host_b = _host_tree(engine), _host_tree(engine_b)
    v2 = _perturb_tree(host_a, seed=1)
    trees_a = {"/ckpt/model_1": host_a, "/ckpt/model_2": v2}
    # replica b's "model_2" is a DIFFERENT tree: the canary must catch it
    trees_b = {"/ckpt/model_1": host_b, "/ckpt/model_2": _perturb_tree(host_b, seed=2)}
    sa, sb = _fleet(engine, engine_b, trees_a, trees_b)
    with sa as server_a, sb as server_b:
        ports = [server_a.port, server_b.port]
        events = []
        updater = _updater(ports, events)

        inflight = []

        def pound(port):
            for _ in range(3):
                _tokens, final = _generate(
                    port, {"prompt": [9, 9, 9], "max_new_tokens": 6}
                )
                inflight.append(final["finish_reason"])

        threads = [threading.Thread(target=pound, args=(p,)) for p in ports]
        for t in threads:
            t.start()
        assert updater.run("/ckpt/model_2") is False
        for t in threads:
            t.join(120)

        names = [e[0] for e in events]
        assert "deploy_canary_fail" in names
        assert "deploy_rollback" in names
        # the WHOLE fleet converged back onto version 1
        for port in ports:
            payload = json.loads(_http(port, "GET", "/healthz")[2])
            assert payload["status"] == "ok"
            assert payload["weights_version"] == 1
            assert payload["weights_checkpoint"] == "/ckpt/model_1"
        # zero dropped requests while the update failed and rolled back
        assert len(inflight) == 6
        assert all(r in ("length", "eos") for r in inflight)


@pytest.mark.faults
def test_crash_mid_update_converges_on_rerun(engine, engine_b, disarm_faults):
    host_a, host_b = _host_tree(engine), _host_tree(engine_b)
    # model_3 is the SAME weights on both replicas: a clean target
    trees_a = {"/ckpt/model_1": host_a, "/ckpt/model_3": _perturb_tree(host_a, seed=1)}
    trees_b = {"/ckpt/model_1": host_b, "/ckpt/model_3": _perturb_tree(host_b, seed=1)}
    sa, sb = _fleet(engine, engine_b, trees_a, trees_b)
    with sa as server_a, sb as server_b:
        ports = [server_a.port, server_b.port]
        events = []
        updater = _updater(ports, events)

        faults.configure("deploy_crash_mid_update", exc=RuntimeError)
        with pytest.raises(RuntimeError, match="deploy_crash_mid_update"):
            updater.run("/ckpt/model_3")
        # mid-update death: the fleet is split across versions
        versions = sorted(
            json.loads(_http(p, "GET", "/healthz")[2])["weights_version"]
            for p in ports
        )
        assert versions == [1, 3]

        # recovery is a plain re-run of the same target: no special casing
        faults.reset()
        assert updater.run("/ckpt/model_3") is True
        assert [e[0] for e in events].count("deploy_complete") == 1
        for port in ports:
            payload = json.loads(_http(port, "GET", "/healthz")[2])
            assert payload["status"] == "ok"
            assert payload["weights_version"] == 3
            assert payload["weights_checkpoint"] == "/ckpt/model_3"
        # engines really swapped: both replicas greedy-agree on the new tree
        outs = [
            _generate(p, {"prompt": [3, 1, 4], "max_new_tokens": 5})[0]
            for p in ports
        ]
        assert outs[0] == outs[1]
