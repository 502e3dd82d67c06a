"""Distributed execution over device meshes.

This package replaces the reference's entire distributed runtime
(/root/reference/veles/server.py, client.py, txzmq/ — a ZeroMQ+Twisted
parameter-server star, SURVEY.md §2.4) with in-program XLA collectives over
a :class:`jax.sharding.Mesh`: data-parallel gradient all-reduce rides ICI
(psum inserted by XLA from sharding annotations), tensor-parallel layer
sharding splits the MXU work, and sequence parallelism (ring attention)
handles long contexts.  The out-of-band job protocol survives separately in
:mod:`veles_tpu.distributed` for the meta-schedulers (ensembles, GA).
"""

from .mesh import make_mesh, data_parallel_sharding, batch_sharding  # noqa
