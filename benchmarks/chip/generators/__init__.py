"""Traffic generators: each reads one kind of traffic file (`traffic/*.json`
names its generator) and draws everything from the seed. The program
receives only what they generate."""
