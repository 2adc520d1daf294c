"""GAT at the widths pytorch_geometric trains it on ogbn-products
(`examples/ogbn_products_gat.py`; OGB leaderboard "GAT (NeighborSampling)",
751,574 parameters): 3 layers of 4 heads, hidden heads of 128
concatenated to 512, output heads of 47 averaged, a linear skip per layer,
ELU between layers, fanout (10, 10, 10). Batch 512 and Adam at lr 1e-3
with no weight decay are the example's training settings.
"""
from repro.configs.base import GNNConfig

CONFIG = GNNConfig(
    name="gat-products",
    model="gat_res",
    num_layers=3,
    hidden_dim=512,
    in_dim=100,
    num_classes=47,
    fanout=(10, 10, 10),
    gat_heads=4,
)
