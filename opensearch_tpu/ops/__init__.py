"""Device kernels (XLA programs) shared by the search path."""

import jax

# Every f32 matmul in the package passes this. On a TPU the default
# precision rounds f32 matmul operands to bf16 (one MXU pass): fine for a
# model's activations, wrong for a search engine's exact answers — an
# "exact" kNN distance, an aggregation's bucket sum. HIGHEST keeps f32
# operands f32 (several passes); on the CPU backend it changes nothing.
F32_MATMUL = jax.lax.Precision.HIGHEST
