"""Sharding: ``FleetMesh`` for the fleet controller, logical-axis rules for the model zoo."""
