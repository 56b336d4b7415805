"""Data substrate: generators of the paper's datasets."""
from .synthetic import (k1_dense_cube, k2_three_cuboids, k3_dense_4d,
                        imdb_like, movielens_like, bibsonomy_like,
                        random_context, semantic_frames_like)
