"""Serving layer: greedy decode, continuous batching server."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import repro.configs as C
from repro.models import model as M
from repro.serve.decode import ServeConfig, Server, greedy_decode


def test_greedy_decode_shapes_and_determinism():
    cfg = C.get_smoke("olmo_1b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(2, cfg.vocab, (2, 4)), jnp.int32)
    a = greedy_decode(params, cfg, prompt, max_new=6, cache_len=32)
    b = greedy_decode(params, cfg, prompt, max_new=6, cache_len=32)
    assert a.shape == (2, 6)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert (np.asarray(a) < cfg.vocab).all()


def test_server_completes_all_requests():
    cfg = C.get_smoke("olmo_1b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    sc = ServeConfig(batch=2, cache_len=64, max_new=5, eos=-1)
    server = Server(params, cfg, sc)
    rng = np.random.default_rng(1)
    rids = [server.submit(rng.integers(2, cfg.vocab, 3).tolist()) for _ in range(5)]
    server.run(n_steps=200)
    assert all(rid in server.done for rid in rids)
    assert all(len(server.done[rid]) == 5 for rid in rids)


def test_replicated_etl_refuses_a_tpu_before_touching_it(monkeypatch):
    """Follower processes cannot share the chip the parent holds: on a TPU
    backend ``serve --etl --replicated`` exits before any parameter lands
    on the device."""
    from repro.launch import serve

    def no_params(*a, **k):
        raise AssertionError("init_params ran before the refusal")

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(M, "init_params", no_params)
    with pytest.raises(SystemExit, match="one process"):
        serve.main(["--smoke", "--etl", "--instances", "2", "--replicated"])


def test_server_slot_reuse():
    cfg = C.get_smoke("olmo_1b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    sc = ServeConfig(batch=1, cache_len=64, max_new=3, eos=-1)
    server = Server(params, cfg, sc)
    r1 = server.submit([5, 6])
    r2 = server.submit([7, 8, 9])
    server.run(n_steps=100)
    assert r1 in server.done and r2 in server.done  # one slot served both
