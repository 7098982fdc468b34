"""User program of the ``criteo_mixed_x4`` configuration.

The same table, features and selector as ``criteo_mixed``, declared by the
same functions: what differs is the deployment
(``configs/criteo_mixed_x4.json``, ``partitions``), 786,432 rows on a host
whose process sees four chips.  Nothing here asks for the partition: the
program takes the 'data'-axis mesh by its own rule
(``parallel.mesh.maybe_data_mesh``) when it sees the devices and the rows.
"""

from .criteo_mixed import CATS, INTS, build, make_data  # noqa: F401
