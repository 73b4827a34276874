from .structure import BSR, CSR, Graph, padded_neighbors, to_bsr, to_csr
from .generators import (PAPER_TABLE7, WebGraphSpec, all_paper_datasets,
                         generate_webgraph, paper_dataset)
from .partition import partition_edges, partition_edges_by_dst_block
from .subgraph import FocusedSubgraph, SubgraphExtractor, root_set_key

__all__ = [
    "BSR", "CSR", "Graph", "padded_neighbors", "to_bsr", "to_csr",
    "PAPER_TABLE7", "WebGraphSpec", "all_paper_datasets",
    "generate_webgraph", "paper_dataset",
    "partition_edges", "partition_edges_by_dst_block",
    "FocusedSubgraph", "SubgraphExtractor", "root_set_key",
]
