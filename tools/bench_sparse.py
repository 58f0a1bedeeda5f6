"""Benchmark the compact sparse paths against their dense equivalents
('record the win or retire the claim').

1. Embedding gradient at big vocab: eager compact row-sparse cotangent
   (O(touched rows)) vs the dense scatter path (O(vocab)).
2. dot(csr, dense): compact gather/segment-sum vs densify-then-matmul.

Prints one JSON line per comparison, on whatever backend jax has (a
CPU timing is not a device number).
"""

import json
import time

import numpy as np


def _sync(x):
    return np.asarray(x)


def bench_embedding_grad(vocab=1_000_000, dim=64, batch=4096, iters=5):
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, nd
    from mxnet_tpu.ops.indexing import sparse_embedding

    rs = np.random.RandomState(0)
    ids = nd.array(rs.randint(0, vocab, (batch,)).astype("float32"))
    weight = nd.random_normal(shape=(vocab, dim))

    def run(sparse):
        weight.attach_grad(stype="row_sparse" if sparse else "default")
        # warmup
        with autograd.record():
            out = (sparse_embedding(ids, weight) if sparse
                   else nd.Embedding(ids, weight, input_dim=vocab,
                                     output_dim=dim))
            loss = (out * out).sum()
        loss.backward()
        g = weight.grad
        _sync(g._rs_values if sparse else g._data)
        t0 = time.perf_counter()
        for _ in range(iters):
            with autograd.record():
                out = (sparse_embedding(ids, weight) if sparse
                       else nd.Embedding(ids, weight, input_dim=vocab,
                                         output_dim=dim))
                loss = (out * out).sum()
            loss.backward()
            g = weight.grad
            _sync(g._rs_values if sparse else g._data)
        dt = (time.perf_counter() - t0) / iters
        rows = (int(g._rs_values.shape[0]) if sparse else vocab)
        return dt, rows

    dt_sparse, rows_sparse = run(True)
    dt_dense, rows_dense = run(False)
    print(json.dumps({
        "bench": "embedding_grad",
        "vocab": vocab, "dim": dim, "batch": batch,
        "sparse_ms": round(dt_sparse * 1e3, 2),
        "dense_ms": round(dt_dense * 1e3, 2),
        "speedup": round(dt_dense / dt_sparse, 2),
        "sparse_grad_rows": rows_sparse,
        "dense_grad_rows": rows_dense,
        "grad_mem_ratio": round(rows_dense / max(rows_sparse, 1), 1),
    }))


def bench_csr_dot(n_rows=4096, dim=100_000, nnz_per_row=32, out_dim=64,
                  iters=5):
    import jax.numpy as jnp

    from mxnet_tpu.ndarray.sparse import csr_dot_dense

    rs = np.random.RandomState(1)
    nnz = n_rows * nnz_per_row
    data = jnp.asarray(rs.standard_normal(nnz).astype(np.float32))
    indices = jnp.asarray(
        rs.randint(0, dim, nnz).astype(np.int32))
    indptr = jnp.asarray(
        np.arange(0, nnz + 1, nnz_per_row).astype(np.int32))
    rhs = jnp.asarray(
        rs.standard_normal((dim, out_dim)).astype(np.float32))

    import jax

    f_sparse = jax.jit(lambda d, i, p, r: csr_dot_dense(
        d, i, p, r, n_rows))

    def dense_form(d, i, p, r):
        rows = (jnp.searchsorted(p, jnp.arange(nnz), side="right") - 1)
        dense = jnp.zeros((n_rows, dim), d.dtype).at[rows, i].add(d)
        return dense @ r

    f_dense = jax.jit(dense_form)

    _sync(f_sparse(data, indices, indptr, rhs))
    _sync(f_dense(data, indices, indptr, rhs))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = f_sparse(data, indices, indptr, rhs)
    _sync(out)
    dt_s = (time.perf_counter() - t0) / iters
    t0 = time.perf_counter()
    for _ in range(iters):
        out = f_dense(data, indices, indptr, rhs)
    _sync(out)
    dt_d = (time.perf_counter() - t0) / iters
    print(json.dumps({
        "bench": "csr_dot",
        "shape": [n_rows, dim], "nnz_per_row": nnz_per_row,
        "out_dim": out_dim,
        "sparse_ms": round(dt_s * 1e3, 2),
        "densify_matmul_ms": round(dt_d * 1e3, 2),
        "speedup": round(dt_d / dt_s, 2),
    }))


if __name__ == "__main__":
    bench_embedding_grad()
    bench_csr_dot()
