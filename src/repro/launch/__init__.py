"""Launchers: device hierarchy, train, serve."""
