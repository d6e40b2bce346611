"""The RMQ service's chip benchmark: harness, traffic, reference, yardsticks."""
