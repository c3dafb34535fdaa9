"""Distributed-execution utilities: logical sharding rules, fault-tolerant
training loops, and elastic rescale planning.

This package was referenced throughout the seed (models, kernels, launch,
train) but absent from it; it is reconstructed here against the behavior the
tests and call sites pin down. Sharding constraints are the identity
outside an `axis_rules` context.
"""
