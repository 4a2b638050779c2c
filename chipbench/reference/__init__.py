"""Plain float32 references, one per model type, named by a configuration's
`model_type`. They import nothing of the program under test."""
