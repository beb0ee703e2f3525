"""The plain reference of the NoC sweep, written from the model's semantics."""
