# Pallas TPU kernels for the framework's compute hot spots. Each kernel
# directory ships kernel.py (pl.pallas_call + BlockSpec VMEM tiling),
# ops.py (jit'd public wrapper), ref.py (pure-jnp oracle checked in tests):
#   flash_attention/  blockwise causal GQA attention (train / prefill)
#   decode_attention/ flash-decoding over long KV caches (serve_step)
#   ssd_scan/         Mamba2 SSD chunked scan (sequential-chunk grid + VMEM state)
#   fused_sgd/        fused momentum-SGD update (the FL ring-hop inner update)
import jax


def default_interpret() -> bool:
    """Interpret mode is for the CPU backend only, where the tests run. Any
    other backend gets the compiled kernel, or the compiler's error."""
    return jax.default_backend() == "cpu"
