from repro.graphs.csr import EdgeList, from_host_edges, degrees, neighbor_matrix
from repro.graphs import generators, segment_ops
