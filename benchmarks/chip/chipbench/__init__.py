"""The chip benchmark's harness, reference, comparison and trace reduction."""
