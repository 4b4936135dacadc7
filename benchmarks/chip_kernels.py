"""Kernels of the chip smoke (``chip_smoke.py``), hinted ``f32``.

Each one is the form of a kernel elsewhere in the repository with its
``f64`` hints changed to ``f32``, the precision the chip computes in:

* :func:`gemm` / :func:`atax` — ``gemm_np`` / ``atax_np`` of
  ``benchmarks/polybench_kernels.py`` (PolyBench/C 4.2.1);
* :func:`stap_adaptive` — ``examples/stap.py``;
* :func:`gemm_rowscale` — ``benchmarks/stap.py``;
* :func:`attn` / :func:`scan` — the attention- and scan-shaped pfor
  kernels the Pallas backend's pattern matcher lowers onto
  ``attention_rows`` / ``scan_rows``.
"""

import numpy as np


def gemm(alpha: float, beta: float, C: "ndarray[f32,2]",
         A: "ndarray[f32,2]", B: "ndarray[f32,2]",
         NI: int, NJ: int, NK: int):
    C[0:NI, 0:NJ] = beta * C[0:NI, 0:NJ] + alpha * np.dot(
        A[0:NI, 0:NK], B[0:NK, 0:NJ])


def atax(A: "ndarray[f32,2]", x: "ndarray[f32,1]", y: "ndarray[f32,1]",
         tmp: "ndarray[f32,1]", M: int, N: int):
    tmp[0:M] = np.dot(A[0:M, 0:N], x[0:N])
    y[0:N] = np.dot(A[0:M, 0:N].T, tmp[0:M])


def stap_adaptive(snap: "ndarray[f32,2]", train: "ndarray[f32,3]",
                  steer: "ndarray[f32,1]", outY: "ndarray[f32,1]",
                  numGates: int, K: int, dof: int, iters: int,
                  alpha: float, loading: float):
    for g in range(0, numGates):
        R = np.dot(train[g, 0:K, 0:dof].T, train[g, 0:K, 0:dof])
        for i in range(0, dof):
            for j in range(0, dof):
                R[i, j] = R[i, j] / K
        w = alpha * steer[0:dof]
        for it in range(0, iters):
            r = steer[0:dof] - np.dot(R[0:dof, 0:dof], w[0:dof]) \
                - loading * w[0:dof]
            w = w + alpha * r[0:dof]
        outY[g] = np.dot(w[0:dof], snap[g, 0:dof])


def gemm_rowscale(A: "ndarray[f32,2]", B: "ndarray[f32,2]",
                  C: "ndarray[f32,2]", n: int, k: int, m: int):
    for i in range(0, n):
        r = 2.0 * A[i, 0:k]
        C[i, 0:m] = np.dot(r, B[0:k, 0:m])


def attn(Q: "ndarray[f32,2]", K: "ndarray[f32,2]", V: "ndarray[f32,2]",
         O: "ndarray[f32,2]", n: int, t: int, d: int):
    for i in range(0, n):
        s = np.dot(K[0:t, 0:d], Q[i, 0:d])
        p = np.exp(s)
        o = np.dot(p, V[0:t, 0:d])
        O[i, 0:d] = o / np.sum(p)


def scan(X: "ndarray[f32,2]", Y: "ndarray[f32,2]", n: int, L: int):
    for i in range(0, n):
        h = 0.0
        for t in range(0, L):
            h = 0.9 * h + X[i, t]
            Y[i, t] = h
