#!/bin/sh
# Build the native runtime pieces into native/lib/.
set -e
cd "$(dirname "$0")"
mkdir -p lib
CXX=${CXX:-g++}
PYINC=$(python3 -c "import sysconfig; print(sysconfig.get_paths()['include'])")
PYLIB=$(python3 -c "import sysconfig; print(sysconfig.get_config_var('LIBDIR'))")
python3 ../tools/gen_c_api.py
python3 ../tools/gen_scalapack_api.py
$CXX -O2 -fPIC -shared -I"$PYINC" -o lib/libslatetpu_c.so c_api.cc c_api_generated.cc -L"$PYLIB" -lpython3.12
$CXX -O2 -fPIC -shared -I"$PYINC" -o lib/libslatetpu_scalapack.so c_api.cc c_api_generated.cc scalapack_api_generated.cc -L"$PYLIB" -lpython3.12
echo "built: $(ls lib)"
