"""Distributed launch plane: host mesh, sharding rules, train/serve steps
and the train, serve and I/O-server entry points."""
