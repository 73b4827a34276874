"""Entry points: the whole-graph ranking job (``rank``), the query service
(``serve_rank``), the compile cache they share, and the mesh builder."""
