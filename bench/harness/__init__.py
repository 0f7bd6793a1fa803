"""The benchmark's own code: registry, traffic, weights, plain reference,
trace reduction, kernel costs. Nothing here is imported by the program."""
