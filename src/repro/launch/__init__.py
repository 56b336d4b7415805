"""Launchers: the tricluster and cluster-serving drivers, the local
mesh and the compile cache they share."""
