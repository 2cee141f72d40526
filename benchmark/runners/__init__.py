"""Runners: how a kind of traffic drives a kind of system. A traffic file
names one under ``runner``; ``run(ctx)`` returns a ``RunRecord``."""
