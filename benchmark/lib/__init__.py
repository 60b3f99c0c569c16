"""The benchmark's yardstick: traffic, counting, weights, references, reducers."""
