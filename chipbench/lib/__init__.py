"""The harness: traffic, weights, reference, timeline and trace reduction."""
