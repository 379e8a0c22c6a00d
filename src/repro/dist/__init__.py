"""Distribution substrate: mesh/sharding specs, distributed clustering,
compressed collectives, and fault tolerance.

This is the layer the paper's closing claim points at — clusters "are also
useful ... for distributing the work over many machines" — realized as four
modules:

* ``sharding``        — PartitionSpec rules for every param/batch/cache tree
                        the launch layer builds, plus the ambient mesh the
                        model's ``shard_map`` paths read
                        (``set_mesh``/``get_active_mesh``).
* ``cluster_dist``    — mesh-sharded SeCluD K-means (``shard_map`` + ``psum``)
                        and adapters that drop it into ``multilevel_cluster``
                        / ``topdown_cluster``.
* ``compression``     — error-feedback int8 gradient compression and the
                        compressed all-reduce built from it.
* ``fault_tolerance`` — straggler detection, mesh-shape planning under device
                        loss, and elastic re-meshing.

Only ``sharding`` is imported eagerly (it is jax-only and consumed by the
model layer); the other modules are plain submodules — import them directly
(``from repro.dist import compression``) to keep import costs where they are
used.
"""

from repro.dist import sharding

__all__ = ["sharding", "cluster_dist", "compression", "fault_tolerance"]
