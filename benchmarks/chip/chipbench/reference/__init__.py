"""Plain float32 jax.numpy references, independent of the program.

They import nothing from ``repro`` and take no value the program made:
weights come from ``chipbench.weights`` and the seed, data from
``chipbench.data``.  Every matmul runs at ``highest`` precision.
"""
