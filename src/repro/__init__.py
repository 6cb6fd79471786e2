"""repro — JAX/Pallas reproduction of "Fast OLAP Query Execution in Main
Memory on Large Data in a Cluster"."""
