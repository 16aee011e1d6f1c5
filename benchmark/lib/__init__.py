"""The benchmark's own library: the yardstick later PRs cannot edit."""
