"""parameter_server_tpu build (role of the reference's make/ build system).

Builds the C++ host library (crc32c, hashing, text parsers) as part of the
package; pure-stdlib build so no pip installs are needed.

    python setup.py build_native   # or: make native
    pip install -e .               # optional editable install
"""

from setuptools import Command, find_packages, setup


class BuildNative(Command):
    description = "build the C++ host library (the loader's own build)"
    user_options = []

    def initialize_options(self):
        pass

    def finalize_options(self):
        pass

    def run(self):
        # through the loader, which names the library after its source,
        # flags and this host's CPU (a bare `make -C cpp` builds a file
        # nothing loads)
        from parameter_server_tpu.cpp import native

        native()


setup(
    name="parameter_server_tpu",
    version="0.1.0",
    description=(
        "TPU-native parameter server framework: sparse linear learners "
        "(async FTRL, darlin block proximal gradient), KV containers over "
        "jax device meshes, NN training through KVLayer, ring attention"
    ),
    packages=find_packages(exclude=("tests",)),
    package_data={"parameter_server_tpu.cpp": ["*.cc", "Makefile"]},
    python_requires=">=3.10",
    # jax/flax/optax/orbax are environment-provided (TPU image); no pins here
    cmdclass={"build_native": BuildNative},
)
