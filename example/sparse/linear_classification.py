"""Sparse logistic regression on CSR features — the reference's
``example/sparse/linear_classification`` recipe on a synthetic
high-dimensional, mostly-empty feature matrix.

What it exercises: ``CSRNDArray`` batch slicing and sparse·dense ``dot``
for the forward pass, a hand-derived row_sparse gradient (only features
present in the batch produce weight rows), and the lazy row_sparse SGD
update that touches ONLY those rows.

TPU-first: the sparse matmul lowers to gather+matmul XLA ops over the
batch's nonzeros; the lazy update is a scatter on touched rows — no
full-width weight traffic per step.

Reference parity: /root/reference/example/sparse/linear_classification/
(weighted CSR data, row_sparse weight pull, lazy SGD).
"""
import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import optimizer as opt_mod
from mxnet_tpu.ndarray import sparse as sp


def make_data(rng, n=512, dim=1000, nnz=12):
    """Each sample touches `nnz` random features; the label depends on a
    hidden weight over a small informative subset."""
    true_w = np.zeros(dim, "float32")
    informative = rng.choice(dim, 50, replace=False)
    true_w[informative] = rng.randn(50) * 2.0
    rows = []
    for _ in range(n):
        idx = rng.choice(dim, nnz, replace=False)
        val = rng.rand(nnz).astype("float32")
        row = np.zeros(dim, "float32")
        row[idx] = val
        rows.append(row)
    x = np.stack(rows)
    y = ((x @ true_w) > 0).astype("float32")
    return x, y


def to_csr(dense):
    """Build the CSRNDArray for a dense batch (host-side featurization)."""
    indptr = [0]
    indices = []
    data = []
    for row in dense:
        nz = np.nonzero(row)[0]
        indices.extend(nz.tolist())
        data.extend(row[nz].tolist())
        indptr.append(len(indices))
    return sp.csr_matrix((np.array(data, "float32"),
                          np.array(indices, "int64"),
                          np.array(indptr, "int64")), shape=dense.shape)


def train(epochs=15, batch_size=64, lr=8.0, seed=0, verbose=True):
    """Returns (first_acc, last_acc)."""
    rng = np.random.RandomState(seed)
    mx.random.seed(seed)
    x, y = make_data(rng)
    dim = x.shape[1]
    w = mx.nd.zeros((dim, 1))
    b = mx.nd.zeros((1,))
    updater = opt_mod.get_updater(
        opt_mod.SGD(learning_rate=lr, rescale_grad=1.0, wd=0.0))

    def forward(xb_csr):
        return mx.nd.sigmoid(sp.dot(xb_csr, w) + b)

    def accuracy():
        p = forward(to_csr(x)).asnumpy().ravel()
        return ((p > 0.5) == y).mean()

    first = accuracy()
    for _ in range(epochs):
        order = rng.permutation(len(x))
        for i in range(0, len(x), batch_size):
            sel = order[i:i + batch_size]
            xb = x[sel]
            yb = y[sel]
            csr = to_csr(xb)
            p = forward(csr).asnumpy().ravel()
            err = mx.nd.array((p - yb).reshape(-1, 1) / len(sel))
            # row_sparse gradient: only rows for features present in the
            # batch — X^T (p - y) restricted to touched feature ids
            touched = np.unique(np.nonzero(xb)[1])
            # the number of touched rows differs in every batch, and each new
            # width would compile the matmul and the update again: pad it to
            # the next power of two with a repeated row id whose gradient is
            # zero, which the lazy update merges away
            n = len(touched)
            ids = np.full(1 << (n - 1).bit_length(), touched[0], "int64")
            ids[:n] = touched
            cols = np.zeros((len(sel), len(ids)), "float32")
            cols[:, :n] = xb[:, touched]
            gw_rows = mx.nd.array(cols).T @ err
            grad = sp.row_sparse_array((gw_rows.asnumpy(), ids),
                                       shape=(dim, 1))
            updater(0, grad, w)                      # lazy: touched rows only
            updater(1, mx.nd.array([float(err.asnumpy().sum())]), b)
    last = accuracy()
    if verbose:
        print(f"sparse-linear accuracy: {first:.3f} -> {last:.3f}")
    return first, last


if __name__ == "__main__":
    train()
