"""Work counts (operations and least bytes) computed from shapes."""
