"""slate_lint: jaxpr- and AST-level static analysis for the distributed
kernels' invariants.

The distributed layer rests on contracts that XLA cannot check for us and
that otherwise surface only as runtime failures on an 8-chip mesh (or
worse, as silent performance/accuracy loss on a pod):

1. every collective rides a declared mesh axis (``ROW_AXIS``/``COL_AXIS``
   from ``parallel/mesh.py``), and collectives traced inside ``fori_loop``
   bodies are covered by an ``audit_scope`` multiplicity so the comm-volume
   audit stays truthful;
2. every floating-point ``dot_general`` in the linalg/parallel kernels
   carries ``Precision.HIGHEST`` (the MXU silently degrades otherwise), and
   no collective payload silently upcasts to f64;
3. donated buffers must actually be aliasable by XLA — an unusable
   donation is a lint failure, not a runtime warning;
4. the block-cyclic maps in ``core/grid.py`` satisfy partition-of-unity
   (every tile owned by exactly one in-range rank, blocksize lambdas sum
   to n).

A second, AST-based pass lints the source itself: raw ``shard_map``
imports or raw ``lax`` collective calls outside ``parallel/comm.py`` (the
audited wrappers exist for a reason), and keywords passed to JAX APIs that
the *installed* JAX signature does not accept — API drift such as the
retired ``check_rep`` keyword, caught before any kernel runs.

Run ``python -m slate_tpu.analysis.lint``; intentional exceptions go in
``slate_tpu/analysis/waivers.cfg``.  The drivers are traced abstractly via
``jax.make_jaxpr`` on a synthetic 8-device CPU mesh — no TPU needed.
"""

from .findings import Finding
from .waivers import Waivers, load_waivers

__all__ = ["Finding", "Waivers", "load_waivers"]
