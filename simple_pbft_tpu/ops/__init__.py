"""TPU-native numeric kernels: GF(2^255-19) limb arithmetic
(``field25519``), Edwards curve point operations (``edwards``), and the
two verify programs: the comb-table double-scalar multiplication kernel
(``comb``) for a key with a table on the device, and the table-free
windowed ladder (``ladder``) for a key without — written in pure jnp
(int32) so they jit/vmap/shard onto TPU."""
