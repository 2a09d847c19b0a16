"""Multi-process dist_sync kvstore worker with known-value checks.

Model: reference ``tests/nightly/dist_sync_kvstore.py`` (``check_diff`` :60)
launched on ONE machine via the local launcher
(``ci/docker/runtime_functions.sh:998-1005``). Here each worker is a
jax.distributed process on the CPU platform; tools/launch.py exports the
JAX_* env trio this script joins the cluster from (via KVStoreDist).

Run directly:   python tools/launch.py -n 2 python tests/dist/dist_sync_kvstore.py
Run from CI:    tests/test_dist.py spawns it and asserts rc == 0.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

# the CPU platform must win before any jax backend init: this test runs
# N cooperating processes on one host, and a chip belongs to one process
os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402


def check_diff(arr, expected):
    """Every element equals the scalar (reference check_diff :60)."""
    np.testing.assert_allclose(arr.asnumpy(),
                               np.full(arr.shape, expected, np.float32),
                               rtol=1e-5)


def main():
    kv = mx.kv.create("dist_sync")
    nw = kv.num_workers
    rank = kv.rank
    assert nw == int(os.environ["JAX_NUM_PROCESSES"]), nw
    assert rank == int(os.environ["JAX_PROCESS_ID"]), rank

    shape = (4, 8)
    big_shape = (64, 64)

    # --- known-value sync push/pull: every worker pushes (rank+1); the
    # store must see the cross-worker sum n(n+1)/2
    kv.init("w", mx.nd.zeros(shape))
    kv.init("big", mx.nd.zeros(big_shape))
    kv.push("w", mx.nd.ones(shape) * (rank + 1))
    out = mx.nd.zeros(shape)
    kv.pull("w", out=out)
    check_diff(out, nw * (nw + 1) / 2)

    # --- aggregated multi-key push with priorities: all queued before any
    # pull, buckets of MXNET_UPDATE_AGGREGATION_SIZE dispatch in priority
    # order; values must still land exactly
    keys = [f"k{i}" for i in range(7)]
    for i, k in enumerate(keys):
        kv.init(k, mx.nd.zeros(shape))
    for i, k in enumerate(keys):
        kv.push(k, mx.nd.ones(shape) * (i + 1), priority=-i)
    outs = [mx.nd.zeros(shape) for _ in keys]
    for k, o in zip(keys, outs):
        kv.pull(k, out=o)
    for i, o in enumerate(outs):
        check_diff(o, nw * (i + 1))

    # --- repeated pushes: without an updater the store holds the LAST
    # reduced push (reference KVStoreLocal assign semantics); both queued
    # pushes flush in order, so the second wins
    kv.push("big", mx.nd.ones(big_shape))
    kv.push("big", mx.nd.ones(big_shape) * 2)
    out = mx.nd.zeros(big_shape)
    kv.pull("big", out=out)
    check_diff(out, 2 * nw)

    # --- update_on_kvstore: server-side optimizer semantics. SGD with
    # lr=1, wd=0 on zero-init weight: w -= sum_of_worker_grads
    kv2_key = "opt"
    kv.init(kv2_key, mx.nd.zeros(shape))
    kv.set_optimizer(mx.optimizer.SGD(learning_rate=1.0, wd=0.0,
                                      rescale_grad=1.0))
    kv.push(kv2_key, mx.nd.ones(shape))
    out = mx.nd.zeros(shape)
    kv.pull(kv2_key, out=out)
    check_diff(out, -1.0 * nw)

    # --- row_sparse_pull returns only touched rows
    kv._updater = None          # back to plain accumulate semantics
    kv.init("rs", mx.nd.ones(shape))
    rid = mx.nd.array([0, 2])
    out = mx.nd.zeros(shape)
    kv.row_sparse_pull("rs", out=out, row_ids=rid)
    got = out.asnumpy()
    assert got[0].sum() == shape[1] and got[2].sum() == shape[1]
    assert got[1].sum() == 0 and got[3].sum() == 0

    # --- 2-bit compressed wire path: every worker pushes 0.6 with
    # threshold 0.5 -> each contributes exactly +0.5, residual 0.1; a second
    # push of 0.45 fires again off the residual (0.55 >= 0.5)
    kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    kv.init("gc", mx.nd.zeros(shape))
    kv.push("gc", mx.nd.ones(shape) * 0.6)
    out = mx.nd.zeros(shape)
    kv.pull("gc", out=out)
    check_diff(out, 0.5 * nw)
    kv.push("gc", mx.nd.ones(shape) * 0.45)
    kv.pull("gc", out=out)
    check_diff(out, 0.5 * nw)

    # --- reduce-scatter-shaped exchange contract (VERDICT r3 #6): the
    # packed payload crosses the wire once per rank (alltoall of 1/N
    # shards), and each rank decodes only ~payload-size bytes no matter
    # how many workers there are — not N x payload as an allgather would
    stats = kv._last_compressed_stats
    payload = stats["payload_bytes"]
    assert payload == 4 * ((shape[0] * shape[1] + 15) // 16), stats
    # decode work per rank == padded payload size, independent of nw
    assert stats["decode_bytes_per_rank"] <= payload + 4 * nw, stats
    assert stats["decode_bytes_per_rank"] < nw * payload or nw == 1, stats
    assert stats["wire_packed_bytes_per_rank"] <= payload + 4 * nw, stats

    # --- liveness surface: everyone is alive, so zero dead nodes
    assert kv.num_dead_node(-1, timeout=60) == 0
    assert kv.num_dead_node(kv.rank, timeout=60) == 0

    # --- barrier flushes and synchronizes
    kv.barrier()
    print(f"worker {rank}/{nw}: dist_sync kvstore OK", flush=True)


if __name__ == "__main__":
    main()
