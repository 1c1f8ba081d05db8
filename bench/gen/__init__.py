"""Input generators: data and weights made from the run's seed."""
