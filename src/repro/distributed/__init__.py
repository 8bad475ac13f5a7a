"""Sharding: datagen chunk-chain sharding and the batch-axis constraint of
the neural-operator consumer."""
from repro.distributed.sharding import ChainSharding, datagen_mesh, shard_act

__all__ = ["ChainSharding", "datagen_mesh", "shard_act"]
