"""``trainer.flash_bwd_kernels``: the reader of the program's counter
``mxtpu_flash_attention_bwd_lowered_total``, beside ``trainer.moe_grouped_layers``
in what it reads and when it reads nothing."""
import os

import jax
import jax.numpy as jnp
import pytest

from chipbench import harness

NAME = "trainer.flash_bwd_kernels"
BENCH = harness.load_json(harness.ROOT, "BENCHMARK.json")


def _reader():
    return harness.load_module(os.path.join(
        harness.HERE, "metrics", NAME + ".py"), "flash_bwd_reader")


class _Counter:
    def __init__(self, **routes):
        self.routes = routes

    def value(self, route):
        return self.routes.get(route, 0)


@pytest.mark.parametrize("routes,want", [
    (dict(pallas=16), 16),            # ouro_2_6b.train's capture
    (dict(pallas=4), 4),              # zaya1_8b.train's
    (dict(scan=4), 0),                # every backward fell back to the scan
    ({}, None),                       # no attention backward traced
])
def test_the_reader_counts_the_pallas_route(monkeypatch, routes, want):
    from mxnet_tpu.observability import catalog
    monkeypatch.setattr(catalog, "FLASH_ATTENTION_BWD_LOWERED",
                        _Counter(**routes))
    assert _reader().read({}) == want


def test_a_program_without_the_counter_reads_nothing(monkeypatch):
    from mxnet_tpu.observability import catalog
    monkeypatch.delattr(catalog, "FLASH_ATTENTION_BWD_LOWERED")
    assert _reader().read({}) is None


def test_a_differentiated_trace_moves_the_reading(monkeypatch):
    monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", "1")
    from mxnet_tpu.ops import pallas_kernels as pk
    q = jnp.ones((1, 2, 16, 128), jnp.float32)
    before = _reader().read({}) or 0
    pk.flash_attention(q, q, q, causal=True)            # no gradient: nothing
    assert (_reader().read({}) or 0) == before
    jax.grad(lambda q: jnp.sum(pk.flash_attention(q, q, q, causal=True)))(q)
    assert _reader().read({}) == before + 1


def test_the_two_decoder_cells_read_it_and_no_other():
    reads = {w["name"] for w in BENCH["workloads"]
             if NAME in harness.metric_readers(BENCH, w["name"])}
    assert reads == {"ouro_2_6b.train", "zaya1_8b.train"}
    entry = [m for m in BENCH["per_layer"] if m["name"] == NAME]
    assert entry == [BENCH["per_layer"][-1]]
    assert entry[0]["layer"] == "Trainer (parallel/data_parallel.py)"
