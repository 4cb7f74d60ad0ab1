"""Pallas kernels with jnp oracles.

Layout (DESIGN.md §5):

  ``bitset_ops.py``     — the universal bitset-kernel library: the
                          masked-popcount pass (``count_stats``), its
                          batched ``uint32[K, n, w]`` variant, and the
                          popcount/row-reduce primitives every problem
                          family binds to;
  ``bitset_degree.py``  — vertex cover's binding of that library;
  ``flash_attention.py``/``ssd_scan.py`` — model-side kernels;
  ``ops.py``            — jitted dispatchers (Pallas off the CPU, jnp
                          oracle on it; interpret mode on the CPU only);
  ``ref.py``            — the pure-jnp oracles each kernel is validated
                          against.
"""
