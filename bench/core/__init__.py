"""The benchmark's own machinery: traffic, weights, the serving loop, trace
reduction, costs and the correctness comparison."""
