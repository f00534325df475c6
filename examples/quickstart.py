"""LEXI quickstart: the paper's observation and codec in 60 seconds.

    PYTHONPATH=src python examples/quickstart.py
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import codec, entropy, fixed
from repro.kernels import ops

rng = np.random.default_rng(0)

# --- 1. the observation (paper §3 / Fig 1) --------------------------------
weights = rng.normal(0, 0.02, 1_000_000).astype(np.float32)
prof = entropy.profile_exponents(weights)
print(f"BF16 exponent entropy : {prof.exp_entropy_bits:.2f} bits  "
      f"(paper: < 3)")
print(f"distinct exponents    : {prof.distinct_exponents}  (paper: < 32)")
print(f"mantissa entropy      : {prof.man_entropy_bits:.2f} bits "
      f"(incompressible)")
print(f"LEXI-H exponent CR    : {prof.exp_cr:.2f}x  (paper: ~3.1x)")
print(f"whole-value CR        : {prof.overall_cr:.2f}x")

# --- 2. Table 2: LEXI vs RLE vs BDI ----------------------------------------
crs = codec.measure_crs(weights)
print(f"\nTable 2 on this tensor: RLE {crs['rle']:.2f}x  "
      f"BDI {crs['bdi']:.2f}x  LEXI {crs['lexi']:.2f}x")

# --- 3. the deployment codec (LEXI-FW): lossless, jit-able -----------------
x = jnp.asarray(rng.normal(0, 1, (256, 1024)), jnp.bfloat16)
ct = fixed.compress(x)
xr = fixed.decompress(ct)
exact = bool(jnp.array_equal(jax.lax.bitcast_convert_type(x, jnp.uint16),
                             jax.lax.bitcast_convert_type(xr, jnp.uint16)))
print(f"\nLEXI-FW roundtrip bit-exact: {exact}; wire ratio "
      f"{ct.ratio():.3f}x; escapes {int(ct.n_escapes)}")

# --- 4. the Pallas kernels (compiled on a TPU, interpreted elsewhere) -----
interp = not ops.on_tpu()
hist = ops.histogram(x, interpret=interp)
print(f"exp_histogram kernel: {int(hist.sum())} values binned "
      f"(== {x.size})")
w = jnp.asarray(rng.normal(0, 0.02, (256, 512)), jnp.bfloat16)
sm, pl, d, nesc = ops.compress_weight(w)
out = ops.matmul_compressed(x[:64, :256], sm, pl, d, interpret=interp)
ref = jnp.dot(x[:64, :256], w, preferred_element_type=jnp.float32)
print(f"decompress_matmul max err vs plain matmul: "
      f"{float(jnp.max(jnp.abs(out - ref))):.2e} (K-block accum order only)")

# --- 5. compressed collective ----------------------------------------------
from jax.sharding import PartitionSpec as P
from repro.core import collectives as cl

if jax.device_count() >= 2:
    n = jax.device_count()
    mesh = jax.make_mesh((n,), ("model",))
    xs = jnp.asarray(rng.normal(0, 1, (n * 8, 128)), jnp.bfloat16)
    f = jax.jit(cl.shmap(
        lambda v: cl.compressed_all_gather(v, "model", cl.CodecConfig()),
        mesh, P("model"), P(None)))
    print(f"compressed all_gather on {n} devices: "
          f"{bool(jnp.array_equal(f(xs), xs))} (bit-exact), wire bytes "
          f"~{1 / fixed.wire_ratio():.2f}x of raw")
else:
    print("single device: run with "
          "XLA_FLAGS=--xla_force_host_platform_device_count=8 "
          "to demo compressed collectives")
