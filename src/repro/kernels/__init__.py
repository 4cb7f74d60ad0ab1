"""Pallas kernels with jnp oracles.

Layout (DESIGN.md §5):

  ``bitset_ops.py``     — the universal bitset-kernel library: the
                          masked-popcount pass (``count_stats``), its
                          batched ``uint32[K, n, w]`` variant, and the
                          popcount/row-reduce primitives every problem
                          family binds to;
  ``bitset_degree.py``  — vertex cover's binding of that library;
  ``autotune.py``       — tile/stages choice per shape and device, and
                          the interpret-mode rule (DESIGN.md §5.6);
  ``ops.py``            — jitted dispatchers (Pallas off the CPU, jnp
                          oracle on it; interpret mode on the CPU only);
  ``ref.py``            — the pure-jnp oracles each kernel is validated
                          against.
"""
