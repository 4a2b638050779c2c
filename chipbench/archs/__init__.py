"""How the program holds each model type, named by a configuration's
`model_type`."""
