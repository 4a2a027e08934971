"""Quickstart: the MoA pipeline end to end in one minute on CPU.

    PYTHONPATH=src python examples/quickstart.py

1. Derive the paper's ONF for a GEMM and dimension-lift it (figs 3-5).
2. Solve block sizes statically from the hardware table (§3.4).
3. Run the Pallas MoA GEMM (interpret mode on CPU) against the oracle.
4. Train a tiny assigned-architecture LM for a few steps.
"""
import numpy as np

import jax
import jax.numpy as jnp

from repro.core import blocking, lifting, onf
from repro.kernels import ops, ref

# -- 1. the algebra ---------------------------------------------------------
m, n, p = 8, 16, 8
o = onf.gemm_onf(m, n, p)
print("== MoA ONF (paper eq. 3) ==")
print(o.render_c())
lifted = onf.gemm_fully_lifted(m, n, p, procs=2, bk=8, bn=4)
print("\n== dimension-lifted (figs 4/5) ==")
print(lifted.render_c())

a = np.random.default_rng(0).standard_normal((m, n))
b = np.random.default_rng(1).standard_normal((n, p))
got = lifted.execute(np.zeros(m * p), a.ravel(), b.ravel())
assert np.allclose(got.reshape(m, p), a @ b)
print("\nlifted ONF == linear algebra: OK")

# -- 2. static blocking -----------------------------------------------------
print("\n== block solver ==")
print("V100 (paper):", blocking.solve_blocks_square(lifting.V100, "float64"),
      "^2 doubles per block")
bc = blocking.solve_blocks(4096, 4096, 4096, "bfloat16")
print("v5e bf16 4096^3:", bc.as_tuple(), f"VMEM {bc.vmem_bytes // 2**20}MiB",
      f"AI {bc.arithmetic_intensity:.0f} flops/B")

# -- 3. the kernel ----------------------------------------------------------
k1, k2 = jax.random.split(jax.random.PRNGKey(0))
A = jax.random.normal(k1, (256, 192), jnp.float32)
B = jax.random.normal(k2, (192, 128), jnp.float32)
C = ops.moa_gemm(A, B)
err = float(jnp.max(jnp.abs(C - ref.gemm_ref(A, B))))
print(f"\nPallas MoA GEMM vs oracle: max err {err:.2e}")
K = ops.kron(jnp.eye(2, dtype=jnp.float32), A[:4, :4])
print("ipophp kron through the same circuit:", K.shape)

# -- 4. a tiny assigned arch ------------------------------------------------
print("\n== 10-step training run (gemma-2b reduced) ==")
from repro.launch.train import main
main(["--arch", "gemma-2b", "--reduced", "--steps", "10", "--batch", "4",
      "--seq", "32", "--log-every", "2"])
